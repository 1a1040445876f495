"""Packed weights+slots layout inside `Trainer.train_many` (ops/sparse.py).

The packed form exists only inside the scan; these tests pin (a) exact
numeric parity against the split-layout step path, (b) the width gate, and
(c) that the state coming out of `train_many` is back in the split layout
(checkpoints/serving/offload never see packed arrays).
"""

import functools

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

import openembedding_tpu as embed
from openembedding_tpu.data import synthetic_criteo
from openembedding_tpu.model import Trainer
from openembedding_tpu.models import make_deepfm
from openembedding_tpu.ops import sparse
from openembedding_tpu.ops.sparse import (apply_ladder, packed_layout,
                                          pack_table,
                                          sparse_apply_dense_table,
                                          sparse_apply_packed_table,
                                          unpack_table)
from openembedding_tpu.parallel import MeshTrainer, make_mesh, sharded

from apply_reference import (ROUNDS_UNDER_JIT, SLOTTED_OPTS,
                             assert_same_table, warm_table)


def test_packed_layout_gate():
    slots = {"accum": jnp.zeros((4, 10), jnp.float32)}
    assert packed_layout(10, slots) == (("accum", 10),)      # 20 <= 32
    assert packed_layout(10, {}) is None                     # no slots
    # 65 + 65 = 130: the padded-copy regime — refuse
    assert packed_layout(65, {"accum": jnp.zeros((4, 65), jnp.float32)}) is None
    # exact lane multiple is fine
    assert packed_layout(64, {"accum": jnp.zeros((4, 64), jnp.float32)}) == \
        (("accum", 64),)
    # non-f32 slots (none exist today; the gate still refuses)
    assert packed_layout(4, {"s": jnp.zeros((4, 4), jnp.bfloat16)}) is None


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((16, 6)), jnp.float32)
    slots = {"a": jnp.asarray(rng.standard_normal((16, 6)), jnp.float32),
             "b": jnp.asarray(rng.standard_normal((16, 1)), jnp.float32)}
    lay = packed_layout(6, slots)
    packed = pack_table(w, slots, lay)
    assert packed.shape == (16, 13)
    w2, s2 = unpack_table(packed, lay, 6, w.dtype)
    np.testing.assert_array_equal(np.asarray(w), np.asarray(w2))
    for k in slots:
        np.testing.assert_array_equal(np.asarray(slots[k]), np.asarray(s2[k]))


@pytest.mark.parametrize("opt", SLOTTED_OPTS, ids=lambda o: o.category)
def test_packed_apply_matches_split(opt):
    """One fused update through both layouts, at a dim at which every
    optimizer's weights and slots pack (width <= 32): two compiled programs
    leave bit-identical tables (for the two optimizers of `ROUNDS_UNDER_JIT`,
    tables a rounding apart), and so do the two applies run op by op, where
    no kernel holds two operations and what is compared is where the layouts
    put a row's columns, for all eight."""
    dim, rows, n = 6, 64, 40
    w, slots, ids, g = warm_table(opt, rows, dim, n, np.random.default_rng(1))
    lay = packed_layout(dim, slots)
    assert lay is not None and dim + sum(k for _, k in lay) <= 32

    def split(w, s):
        return sparse_apply_dense_table(opt, w, s, ids, g)

    def packed(w, s):
        out, _ = sparse_apply_packed_table(opt, pack_table(w, s, lay), lay,
                                           dim, ids, g)
        return unpack_table(out, lay, dim, w.dtype)

    want = jax.jit(split)(w, slots)
    assert (np.asarray(want[0]) != np.asarray(w)).any()
    assert_same_table(want, jax.jit(packed)(w, slots),
                      exact=opt.category not in ROUNDS_UNDER_JIT)
    with jax.disable_jit():
        assert_same_table(split(w, slots), packed(w, slots))


def test_train_many_packed_matches_step_loop():
    """`jit_train_many` (packed scan) == sequential `jit_train_step` (split):
    same losses, same final tables, and the returned state is split-layout."""
    V, steps = 2048, 6
    model = make_deepfm(vocabulary=V, dim=8)
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.05))
    batches = list(synthetic_criteo(64, id_space=V, steps=steps, seed=5))
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)

    state = trainer.init(batches[0])
    # sanity: this model/optimizer combination actually engages packing
    assert trainer._packed_layouts(state), "expected a packable table"

    sm, metrics = trainer.jit_train_many()(state, stacked)
    assert metrics["loss"].shape == (steps,)

    state2 = trainer.init(batches[0])
    step = trainer.jit_train_step()
    losses = []
    for b in batches:
        state2, m = step(state2, b)
        losses.append(float(m["loss"]))

    np.testing.assert_allclose(np.asarray(metrics["loss"]), losses,
                               rtol=0, atol=0)
    (name, spec), = model.ps_specs().items()
    # split layout on exit: weights have the spec's width again
    assert sm.tables[name].weights.shape[1] == spec.output_dim
    assert set(sm.tables[name].slots) == set(state2.tables[name].slots)
    np.testing.assert_array_equal(np.asarray(sm.tables[name].weights),
                                  np.asarray(state2.tables[name].weights))
    for k, v in state2.tables[name].slots.items():
        np.testing.assert_array_equal(np.asarray(sm.tables[name].slots[k]),
                                      np.asarray(v))


def test_train_many_packed_hash_table_matches_step_loop():
    """Hash-table (input_dim=-1) variables pack too: same probe/insert/
    overflow semantics, one gather/scatter pair. Exact parity vs the split
    step path, including the keys array and overflow counter."""
    from openembedding_tpu.embedding import Embedding
    from openembedding_tpu.model import EmbeddingModel
    from openembedding_tpu.models.ctr import LogisticRegression

    steps = 5
    model = EmbeddingModel(
        module=LogisticRegression(),
        embeddings=[Embedding(input_dim=-1, output_dim=8, name="categorical",
                              capacity=512)])
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.1))
    rng = np.random.default_rng(11)
    batches = [{"sparse": {"categorical": rng.integers(0, 10_000, (32, 4))
                           .astype(np.int64)},
                "dense": None,
                "label": rng.integers(0, 2, (32,)).astype(np.float32)}
               for _ in range(steps)]
    stacked = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs) if xs[0] is not None else None, *batches,
        is_leaf=lambda x: x is None)

    state = trainer.init(batches[0])
    assert "categorical" in trainer._packed_layouts(state)
    sm, metrics = trainer.jit_train_many()(state, stacked)

    state2 = trainer.init(batches[0])
    step = trainer.jit_train_step()
    losses = []
    for b in batches:
        state2, m = step(state2, b)
        losses.append(float(m["loss"]))

    np.testing.assert_allclose(np.asarray(metrics["loss"]), losses,
                               rtol=0, atol=0)
    np.testing.assert_array_equal(np.asarray(sm.tables["categorical"].keys),
                                  np.asarray(state2.tables["categorical"].keys))
    assert int(sm.tables["categorical"].overflow) == int(state2.tables["categorical"].overflow)
    np.testing.assert_array_equal(np.asarray(sm.tables["categorical"].weights),
                                  np.asarray(state2.tables["categorical"].weights))
    for k, v in state2.tables["categorical"].slots.items():
        np.testing.assert_array_equal(np.asarray(sm.tables["categorical"].slots[k]),
                                      np.asarray(v))


# name -> (devices, wire, what the batches hold). "eight_shards" is the family's
# first member: DeepFM, no ladder (a small table), the scan against the step
# loop. The others are the owner's shared plan (`parallel/sharded.py` "THE
# OWNER PLANS ONCE A STEP") at S = 4 with the ladder engaged: a device has
# 512 positions, so W = 512 and apply_ladder(512) = (128, 256, 384, 512)
_MESH_CASES = {
    "eight_shards": (8, "fp32", "criteo"),
    "four_shards_compact_fp32": (4, "fp32", "spread"),
    "four_shards_compact_bf16": (4, "bf16", "spread"),
    "four_shards_full_size_step": (4, "fp32", "one_owner"),
    "four_shards_bad_ids_bf16": (4, "bf16", "bad_ids"),
    # a migration annex and a replicated hot cache beside the shard: their
    # rows are served and applied apart, the plan leaves them out
    "four_shards_annex_and_hot": (4, "fp32", "placed"),
    # error feedback writes `state.ef`, not the weights (against the
    # plan-less scan alone: the step loop draws the rounding another way)
    "four_shards_int8_error_feedback": (4, "int8", "spread"),
    # nothing to compact and no conditional: the plan rides as it is
    "one_shard": (1, "fp32", "spread"),
}
_MB, _MF, _MV, _MK = 256, 8, 4096, 3     # 2,048 positions a step, 512 a device
_mesh_trainers, _mesh_seen = {}, []


def _watched_apply(opt, packed, layout, dim, row_ids, grads, pre_counts=None,
                   *, plan=None):
    """`sparse_apply_packed_table` that leaves in `_mesh_seen` what the
    owner's apply is handed beside a plan, a record a shard a step. ONE
    function and one list a process: a trainer the family shares runs the
    callback it was traced with."""
    if plan is not None:
        mask = plan.counts[plan.uniq.inverse] > 0   # the slots it kept
        ladder = jnp.asarray(apply_ladder(row_ids.shape[0])[:-1])
        counts = jnp.where(plan.counts > 0,
                           plan.uniq.segment_reduce(pre_counts), 0)
        jax.debug.callback(
            lambda *r: _mesh_seen.append([np.asarray(x) for x in r]),
            jnp.all((pre_counts > 0) == mask), jnp.sum(mask),
            jnp.sum(jnp.sum(counts > 0) > ladder),
            jnp.sum(jnp.sum(plan.counts > 0) > ladder))
    return sparse_apply_packed_table(opt, packed, layout, dim, row_ids, grads,
                                     pre_counts, plan=plan)


_SERVE_ROWS = sharded._serve_rows


def _serve_per_slot(*a, **kw):
    """In `sharded._serve_rows`' place: the owner makes no plan (the program
    as it was before PR 39)."""
    return _SERVE_ROWS(*a, **{**kw, "share": False})


def _patched():
    """Everything this family puts in the package's place while a program
    traces: part of a shared trainer's key, so a case that patches anything
    else (or another spy) never gets a program traced without it."""
    return (sparse.sparse_apply_packed_table, sharded._serve_rows,
            sparse.FAST_MEMORY_BYTES)


def _mesh_batches(holds):
    """K batches over `_MV` ids. "spread" and "placed": Zipf-like duplicates
    over every owner; "one_owner": ids that are all shard 0's (multiples of
    4), 300 and more distinct a device, so that shard receives over W = 512
    and works full size while the other three receive nothing; "bad_ids":
    negative, out-of-vocabulary and padding ids among the valid ones."""
    rng = np.random.default_rng(39)
    out = []
    for _ in range(_MK):
        if holds == "one_owner":
            ids = 4 * rng.integers(0, _MV // 4, (_MB, _MF))
        else:
            ids = (rng.zipf(1.3, (_MB, _MF)) * 7919 + rng.integers(
                0, 64, (_MB, _MF))) % _MV
        ids = ids.astype(np.int64)
        if holds == "bad_ids":
            bad = rng.random((_MB, _MF))
            ids = np.where(bad < 0.1, -1, ids)                      # padding
            ids = np.where((bad >= 0.1) & (bad < 0.2),
                           -2 - rng.integers(0, 5, ids.shape), ids)
            ids = np.where((bad >= 0.2) & (bad < 0.3),
                           _MV + rng.integers(0, 3 * _MV, ids.shape), ids)
            ids[0, :3] = [_MV, 2**31 - 1, _MV + 3]
        out.append({"sparse": {"emb": ids}, "dense": None,
                    "label": rng.integers(0, 2, (_MB,)).astype(np.float32)})
    return out


@pytest.mark.parametrize("case", sorted(_MESH_CASES))
def test_mesh_train_many_packed_matches_step_loop(case, monkeypatch):
    """MeshTrainer's scan packs per shard: jit_train_many (packed, the owner's
    serve planning the step and its apply reusing the plan) == sequential
    jit_train_step (split) on the same mesh — losses and final sharded tables
    exact. The four-shard cases also hold it to the plan-less scan (the
    serve per slot, the apply with its own dedup and gather: the program as
    it was), at both wires, in a step that fits the owner's working size and
    in one that does not, and watch what the apply is handed beside a plan:
    counts positive exactly on the slots the plan kept (the others it routed
    to its sentinel), and a rung that is the plan's."""
    shards, wire, holds = _MESH_CASES[case]
    mesh = make_mesh(jax.devices()[:shards])
    if holds == "criteo":
        batches = list(synthetic_criteo(64, id_space=4096, steps=4, seed=13))

        def trainer(role=""):
            return MeshTrainer(make_deepfm(vocabulary=4096, dim=8),
                               embed.Adagrad(learning_rate=0.05), mesh=mesh)
    else:
        monkeypatch.setattr(sparse, "FAST_MEMORY_BYTES", 0)
        assert apply_ladder(_MB * _MF // 4) == (128, 256, 384, 512)
        batches = _mesh_batches(holds)

        placed = 16 if holds == "placed" else 0

        def trainer(role=""):
            # where nothing is placed by hand, ONE trainer a (shards, wire,
            # role, what is patched) a process: a `MeshTrainer` keeps its
            # jitted scan and step, so two cases that differ in their ids
            # alone (compact and full-size step; sound and bad ids) share
            # the three programs
            key = (shards, wire, role, _patched())
            if not placed and key in _mesh_trainers:
                return _mesh_trainers[key]
            layer = embed.Embedding(_MV, _PDIM, name="emb")
            tr = MeshTrainer(embed.EmbeddingModel(_BagTower(), [layer]),
                             embed.Adagrad(learning_rate=0.1), seed=2,
                             mesh=mesh, wire=wire, hot_rows=placed,
                             mig_rows=placed)
            return tr if placed else _mesh_trainers.setdefault(key, tr)
    stacked = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs) if xs[0] is not None else None, *batches,
        is_leaf=lambda x: x is None)

    seen = _mesh_seen
    del seen[:]
    monkeypatch.setattr(sparse, "sparse_apply_packed_table", _watched_apply)

    def init(tr):
        state = tr.init(batches[0])
        if holds == "placed":   # ids of the Zipf head: every batch holds them
            head = np.unique(batches[0]["sparse"]["emb"])[:24]
            state = tr.refresh_hot_rows(state, hot_ids={"emb": head[:8]})
            state = tr.migrate_rows(state, moves={"emb": (
                head[8:], ((head[8:] + 1) % shards).astype(np.int32))})
        return state

    def many(tr):
        state = init(tr)
        assert tr._packed_layouts(state), "expected a packable table"
        return tr.jit_train_many(stacked, state)(state, stacked)

    sm, metrics = many(trainer("planned"))
    jax.effects_barrier()
    assert seen, "no apply took the owner's plan"
    assert all(same for same, *_ in seen)
    assert all(apply_rung == plan_rung for _, _, apply_rung, plan_rung in seen)
    if holds != "criteo":
        # a shard a step that does not fit makes and takes no plan
        full_steps = sum(int(v) for v in metrics["owner_full_steps"].values())
        assert full_steps == (_MK if holds == "one_owner" else 0)
        assert len(seen) == shards * _MK - full_steps
        assert (max(n for _, n, *_ in seen) > 0) == (holds != "one_owner")

    trainer2, others = trainer("step_loop"), []
    if wire != "int8":
        state2 = init(trainer2)
        step = trainer2.jit_train_step(batches[0], state2)
        losses = []
        for b in batches:
            state2, m = step(state2, b)
            losses.append(np.asarray(m["loss"]))
        others.append((state2, np.stack(losses)))

    if holds != "criteo":
        before = len(seen)
        with monkeypatch.context() as m:
            m.setattr(sharded, "_serve_rows", _serve_per_slot)
            plan_less, pm = many(trainer("plan_less"))
        jax.effects_barrier()
        assert len(seen) == before      # no plan made, none taken
        others.append((plan_less, np.asarray(pm["loss"])))

    for other, their_losses in others:
        np.testing.assert_array_equal(np.asarray(metrics["loss"]),
                                      their_losses)
        for a, b in zip(jax.tree_util.tree_leaves(sm.tables),
                        jax.tree_util.tree_leaves(other.tables)):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    (name, spec), = trainer2.model.ps_specs().items()
    # split layout on exit, and a table that moved
    assert sm.tables[name].weights.shape[1] == spec.output_dim
    fresh = init(trainer2).tables[name]
    assert not np.array_equal(np.asarray(sm.tables[name].weights),
                              np.asarray(fresh.weights))
    if holds == "placed":   # ... and so did the annex and the hot cache
        for moved in ("mig", "hot"):
            assert not np.array_equal(
                np.asarray(getattr(sm.tables[name], moved).weights),
                np.asarray(getattr(fresh, moved).weights))


def test_mesh_train_many_packed_hash(tmp_path):
    """Hash tables on the mesh pack too (probe/insert/overflow unchanged);
    checkpoint saved from the post-scan state restores identically."""
    from openembedding_tpu.embedding import Embedding
    from openembedding_tpu.model import EmbeddingModel
    from openembedding_tpu.models.ctr import LogisticRegression

    steps = 3
    model = EmbeddingModel(
        module=LogisticRegression(),
        embeddings=[Embedding(input_dim=-1, output_dim=8, name="categorical",
                              capacity=2048)])
    mesh = make_mesh()
    trainer = MeshTrainer(model, embed.Adagrad(learning_rate=0.1), mesh=mesh)
    rng = np.random.default_rng(17)
    batches = [{"sparse": {"categorical": rng.integers(0, 100_000, (32, 4))
                           .astype(np.int64)},
                "dense": None,
                "label": rng.integers(0, 2, (32,)).astype(np.float32)}
               for _ in range(steps)]
    stacked = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs) if xs[0] is not None else None, *batches,
        is_leaf=lambda x: x is None)

    state = trainer.init(batches[0])
    many = trainer.jit_train_many(stacked, state)
    sm, metrics = many(state, stacked)
    assert np.isfinite(np.asarray(metrics["loss"])).all()

    trainer2 = MeshTrainer(model, embed.Adagrad(learning_rate=0.1), mesh=mesh)
    state2 = trainer2.init(batches[0])
    step = trainer2.jit_train_step(batches[0], state2)
    for b in batches:
        state2, m = step(state2, b)
    np.testing.assert_array_equal(
        np.asarray(sm.tables["categorical"].keys),
        np.asarray(state2.tables["categorical"].keys))
    np.testing.assert_array_equal(
        np.asarray(sm.tables["categorical"].weights),
        np.asarray(state2.tables["categorical"].weights))

    # post-scan state checkpoints in the normal split format; compare via
    # eval (host-side key re-insertion may place rows in different slots —
    # slot positions are an implementation detail, lookups are the contract)
    ck = str(tmp_path / "ck")
    trainer.save(sm, ck)
    state3 = trainer.load(trainer.init(batches[0]), ck)
    ev = trainer.jit_eval_step(batches[0], sm)
    a = np.asarray(ev(sm, batches[0])["logits"])
    c = np.asarray(ev(state3, batches[0])["logits"])
    np.testing.assert_array_equal(a, c)


def test_train_many_unpackable_still_works():
    """A packed width in XLA's padded-copy regime (32 < W < 128) bypasses
    packing; train_many still runs on the split layout."""
    V, steps = 512, 3
    # dim 33 -> table width 34 (folded first-order col), +34 accum = 68: gated
    model = make_deepfm(vocabulary=V, dim=33)
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.05))
    batches = list(synthetic_criteo(32, id_space=V, steps=steps, seed=9))
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)
    state = trainer.init(batches[0])
    assert trainer._packed_layouts(state) == {}
    sm, metrics = trainer.jit_train_many()(state, stacked)
    assert np.isfinite(np.asarray(metrics["loss"])).all()


def _count_table_scatters(txt, shape):
    """Scatters producing a f32[shape] table, across XLA lowerings: the
    native `scatter(` op, or (CPU backends that expand scatter) a `while`
    loop carrying the table whose metadata records the originating scatter."""
    import re

    direct = re.findall(rf"= f32\[{shape}\]\S* scatter\(", txt)
    lowered = [l for l in txt.splitlines()
               if re.search(rf"%while\.\d+ = \(s32\[\], f32\[{shape}\]", l)
               and "/scatter" in l]
    return len(direct) + len(lowered)


@pytest.fixture
def ladder(request, monkeypatch):
    """"small_table": a table under `FAST_MEMORY_BYTES` keeps the program as
    it was (no switch); "ladder": the gate lifted, as for a table of 128 MiB
    and more. -> the scatters a table then compiles to."""
    if request.param == "ladder":
        monkeypatch.setattr(sparse, "FAST_MEMORY_BYTES", 0)
        return len(apply_ladder(256 * 26))
    return 1


@pytest.mark.parametrize("ladder", ["small_table", "ladder"], indirect=True)
def test_packed_scan_compiles_one_scatter_per_table(ladder):
    """Structural pin on the packed win: the compiled train_many updates the
    table through ONE scatter into the packed (V, 20) array (with the apply's
    choice of a working size, one in each of its branches, of which a step
    runs one) — never the two split-layout scatters ((V, 10) weights +
    (V, 10) accum) — and temps stay far below a second table copy,
    conditional and all. HLO-shape matching is deliberately narrow;
    if an XLA upgrade reshuffles instruction names, update the patterns, but
    a reappearing split-shape scatter or a table-sized temp is a real
    regression."""
    V = 1 << 18
    model = make_deepfm(vocabulary=V, dim=9)
    tr = Trainer(model, embed.Adagrad(learning_rate=0.05))
    batches = list(synthetic_criteo(256, id_space=V, steps=2, seed=1))
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)
    state = tr.init(batches[0])
    compiled = jax.jit(tr.train_many, donate_argnums=(0,)).lower(
        state, stacked).compile()

    txt = compiled.as_text()
    packed = _count_table_scatters(txt, f"{V},20")
    lines = _count_table_scatters(txt, f"{V // 4},128")
    split = _count_table_scatters(txt, f"{V},10")
    assert ladder in (1, 4)
    # a table that takes the ladder is held four rows a lane line
    # (`ops.sparse.takes_lines`) and written ONCE, after the switch
    assert (packed, lines) == ((1, 0) if ladder == 1 else (0, 1)), \
        f"packed-table scatters: {packed} of rows, {lines} of lines"
    assert (" conditional(" in txt) == (ladder > 1)
    assert split == 0, f"split-layout scatters reappeared: {split}"

    ma = compiled.memory_analysis()
    if ma is not None:  # backend-dependent
        packed_bytes = V * (20 if ladder == 1 else 32) * 4
        assert ma.temp_size_in_bytes < 3 * packed_bytes, (
            f"temps {ma.temp_size_in_bytes} suggest an extra table copy "
            f"inside the scan (packed table is {packed_bytes})")


@pytest.mark.parametrize("ladder", ["small_table", "ladder"], indirect=True)
def test_packed_scan_dim64_split_first_order_one_scatter_each(ladder):
    """The dim-64 benchmark configuration: split
    first-order auto-engages at lane-multiple dims, so train_many packs BOTH
    tables — categorical 64+64 -> (V, 128) lane-exact, first_order 1+1 ->
    (V, 2) sublane — and each updates through ONE packed scatter with no
    split-shape scatters left. The on-chip HBM claim (no 128-lane-padded temp
    copy of the table at width 128) needs a chip run (the `deepfm64.train_zipf`
    cell of `benchmark/`, and `tests/test_tpu_compile.py` for a described
    v5e); this pins the program STRUCTURE on any backend."""
    V = 1 << 14
    model = make_deepfm(vocabulary=V, dim=64)
    assert set(model.specs) == {"categorical", "first_order"}
    tr = Trainer(model, embed.Adagrad(learning_rate=0.05))
    batches = list(synthetic_criteo(256, id_space=V, steps=2, seed=1))
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)
    state = tr.init(batches[0])
    assert set(tr._packed_layouts(state)) == {"categorical", "first_order"}
    compiled = jax.jit(tr.train_many, donate_argnums=(0,)).lower(
        state, stacked).compile()

    txt = compiled.as_text()
    cat = _count_table_scatters(txt, f"{V},128")
    fo = _count_table_scatters(txt, f"{V},2")
    split = (_count_table_scatters(txt, f"{V},64")
             + _count_table_scatters(txt, f"{V},65")
             + _count_table_scatters(txt, f"{V},1"))
    # with the ladder one scatter a rung, of which a step runs one; a table
    # of one lane line a row (`ops.sparse.takes_row_dmas`) is written ONCE,
    # after the switch, from the rows its rungs hand on
    assert cat == 1, f"expected 1 packed categorical scatter, found {cat}"
    assert fo == ladder, \
        f"expected {ladder} packed first-order scatter(s), found {fo}"
    assert split == 0, f"split-layout scatters reappeared: {split}"


def test_seq_mesh_train_many_packed_matches_step_loop():
    """SeqMeshTrainer (context parallelism) inherits the packed scan hooks:
    a SASRec with a packable item table (dim 16 + Adagrad accum = 32) runs
    jit_train_many on the packed per-shard layout and matches the per-step
    split path exactly on the same (data, seq) mesh."""
    from jax.sharding import Mesh
    from openembedding_tpu.models import make_sasrec, synthetic_sequences
    from openembedding_tpu.parallel import SeqMeshTrainer

    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devices, ("data", "seq"))
    steps = 3

    def build():
        model = make_sasrec(512, 16, attention="ring")
        return model, SeqMeshTrainer(model, embed.Adagrad(learning_rate=0.1),
                                     mesh=mesh)

    batches = list(synthetic_sequences(8, 16, 512, steps=steps, seed=21))
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)

    model, tr = build()
    state = tr.init(batches[0])
    assert tr._packed_layouts(state), "expected the item table to pack"
    many = tr.jit_train_many(stacked, state)
    sm, metrics = many(state, stacked)

    model2, tr2 = build()
    state2 = tr2.init(batches[0])
    step = tr2.jit_train_step(batches[0], state2)
    losses = []
    for b in batches:
        state2, m = step(state2, b)
        losses.append(float(m["loss"]))

    np.testing.assert_allclose(np.asarray(metrics["loss"]), losses,
                               rtol=0, atol=0)
    for name in model.ps_specs():
        np.testing.assert_array_equal(
            np.asarray(sm.tables[name].weights),
            np.asarray(state2.tables[name].weights))


# -- the apply over the unique prefix (ops/sparse.py "WHAT THE APPLY WORKS
# OVER"): gather, row math and scatter run over the smallest rung of
# `apply_ladder(n)` that holds the step's valid unique rows, and leave the
# table of the full-size path (the ladder patched to `(n,)`: no switch is
# traced, the code as it was) bit for bit. Tables under `FAST_MEMORY_BYTES`
# are left alone, so these tests lift that gate ------------------------------

_N, _ROWS, _DIM = 512, 1024, 8          # apply_ladder(512) = (128, 256, 384, 512)
# name -> distinct valid rows among the n positions
_ID_CASES = {"all_equal": 1, "share_0.2": 102, "share_0.6": 307,
             "share_0.9": 461, "all_distinct": _N, "exactly_W": 256,
             "W_plus_1": 257, "invalid_mixed": 300}
_KINDS = ("array_packed", "array_split", "hash_packed", "hash_split",
          "bf16_split")


def _prefix_fn(kind):
    """The apply of a table of `kind` over (the table, ids, g, pre) -> (what
    it leaves, the step's load). Every case of a kind has the same shapes."""
    from openembedding_tpu.tables.hash_table import (
        hash_apply_gradients, hash_apply_gradients_packed)
    opt = embed.Adagrad(learning_rate=0.1)
    if kind == "hash_split":
        def fn(st, ids, g, pre):
            st, load = hash_apply_gradients(st, opt, ids, g, with_load=True)
            return (st.keys, st.weights, st.slots), load
    elif kind == "hash_packed":
        def fn(st, ids, g, pre):
            lay = packed_layout(_DIM, st.slots)
            st = st.replace(weights=pack_table(st.weights, st.slots, lay),
                            slots={})
            st, load = hash_apply_gradients_packed(st, opt, ids, g, lay, _DIM)
            return (st.keys, st.weights), load
    elif kind == "array_packed":
        def fn(table, ids, g, pre):
            w, s = table
            lay = packed_layout(_DIM, s)
            return sparse_apply_packed_table(
                opt, pack_table(w, s, lay), lay, _DIM, ids, g, pre)
    else:
        def fn(table, ids, g, pre):
            w, s, load = sparse_apply_dense_table(opt, *table, ids, g, pre,
                                                  with_load=True)
            return (w, s), load
    return fn


@functools.lru_cache(maxsize=None)
def _prefix_program(kind, laddered):
    """ONE jitted apply a (kind, with the ladder or with it patched to
    `(n,)`): the ids, gradients and counts are arguments, so the cases of a
    kind share the two programs (as `tests/test_packed_lines.py::
    _apply_program` does)."""
    return jax.jit(_prefix_fn(kind))


def _prefix_run(kind, laddered, args, monkeypatch):
    """The patches are on during every call: the first one traces."""
    with monkeypatch.context() as m:
        m.setattr(sparse, "FAST_MEMORY_BYTES", 0)  # these are KiB
        if not laddered:
            m.setattr(sparse, "apply_ladder", lambda n: (n,))
        return jax.device_get(_prefix_program(kind, laddered)(*args))


def _prefix_case(kind, case):
    """-> ((the table, ids, g, pre_counts), the host's count of valid unique
    rows). `invalid_mixed` plants negative ids and, on array tables,
    `pre_counts` of 0 (on hash tables: ids the pull never inserted, which the
    apply gives count 0), all outside the `_ID_CASES[case]` rows that stay
    valid."""
    from openembedding_tpu.embedding import (EmbeddingSpec, init_table_state,
                                             lookup_train)
    rng = np.random.default_rng(sorted(_ID_CASES).index(case))
    u = _ID_CASES[case]
    hashed = kind.startswith("hash")
    space = (1 << 40) if hashed else _ROWS
    pool = rng.choice(space, size=u + 64, replace=False) if hashed \
        else rng.permutation(_ROWS)[:u + 64]
    pool, spare = pool[:u], pool[u:]
    ids = rng.permutation(np.concatenate([pool, rng.choice(pool, _N - u)]))
    pre = np.ones((_N,), np.int32)
    inserted = ids
    if case == "invalid_mixed":
        # 150 positions go invalid; every valid row keeps one position
        first = np.unique(ids, return_index=True)[1]
        free = np.setdiff1d(np.arange(_N), first)
        bad = rng.choice(free, 150, replace=False)
        ids[bad[:50]] = -1 - rng.integers(0, 5, 50)        # negative ids
        ids[bad[50:]] = rng.choice(spare, 100)              # rows nobody ...
        if hashed:
            inserted = np.delete(ids, bad)                  # ... pulled
        else:
            pre[bad[50:]] = 0                               # ... counts
    assert np.unique(ids[(ids >= 0) & (pre > 0) & np.isin(ids, pool)]).size == u
    ids = jnp.asarray(ids, jnp.int64 if hashed else jnp.int32)
    g = jnp.asarray(rng.standard_normal((_N, _DIM)), jnp.float32)
    opt = embed.Adagrad(learning_rate=0.1)
    if hashed:
        spec = EmbeddingSpec("t", -1, _DIM, capacity=4096)
        state, _ = lookup_train(spec, init_table_state(spec, opt),
                                jnp.asarray(inserted, jnp.int64))
        assert int(state.overflow) == 0
        return (state, ids, g, None), u
    dtype = jnp.bfloat16 if kind == "bf16_split" else jnp.float32
    w = jnp.asarray(rng.standard_normal((_ROWS, _DIM)), dtype)
    return ((w, opt.init_slots(_ROWS, _DIM)), ids, g, jnp.asarray(pre)), u


@pytest.mark.parametrize("case", sorted(_ID_CASES))
@pytest.mark.parametrize("kind", _KINDS)
def test_apply_over_the_unique_prefix_is_the_full_size_apply(
        kind, case, monkeypatch):
    assert apply_ladder(_N) == (128, 256, 384, _N)
    args, n_valid = _prefix_case(kind, case)
    got, load = _prefix_run(kind, True, args, monkeypatch)
    want, full = _prefix_run(kind, False, args, monkeypatch)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the load is the host's count, and the rung the smallest that holds it
    np.testing.assert_array_equal(load["apply_fill"],
                                  np.float32(n_valid) / np.float32(_N))
    assert int(load["apply_full_steps"]) == int(n_valid > 384)
    # one rung leaves nothing to overrun: the nulled path counts no step
    assert int(full["apply_full_steps"]) == 0
    np.testing.assert_array_equal(full["apply_fill"], load["apply_fill"])


def test_apply_ladder_rule():
    """Quarters of n, each rounded up to a multiple of 128 and clamped to n,
    equal rungs merged; the last rung is always n."""
    assert apply_ladder(106496) == (26624, 53248, 79872, 106496)
    assert apply_ladder(8192) == (2048, 4096, 6144, 8192)
    assert apply_ladder(416) == (128, 256, 384, 416)
    assert apply_ladder(300) == (128, 256, 300)
    assert apply_ladder(104) == (104,)      # too short to split: no switch
    for n in (1, 127, 128, 129, 1000, 4097, 106496):
        lad = apply_ladder(n)
        assert lad[-1] == n and list(lad) == sorted(set(lad))
        assert all(r % 128 == 0 for r in lad[:-1])


@pytest.mark.parametrize("gate,switched", [(None, False), (0, True)],
                         ids=["table_under_fast_memory", "gate_lifted"])
def test_a_small_table_traces_no_switch(gate, switched, monkeypatch):
    """A table the compiler can keep in fast memory (under
    `FAST_MEMORY_BYTES`) gets the program as it was; a buffer too short to
    split (n <= 128) likewise, whatever the table."""
    if gate is not None:
        monkeypatch.setattr(sparse, "FAST_MEMORY_BYTES", gate)
    opt = embed.Adagrad(learning_rate=0.1)
    w = jnp.zeros((_ROWS, _DIM), jnp.float32)
    slots = opt.init_slots(_ROWS, _DIM)

    def text(n):
        ids, g = jnp.zeros((n,), jnp.int32), jnp.zeros((n, _DIM), jnp.float32)
        return jax.jit(lambda w, s: sparse_apply_dense_table(
            opt, w, s, ids, g)).lower(w, slots).as_text()
    assert ("stablehlo.case" in text(_N)) == switched
    assert "stablehlo.case" not in text(128)


# -- one dedup and one table gather a table a step (ops/sparse.py "ONE DEDUP
# AND ONE TABLE GATHER A STEP"): an array table's packed scan plans before it
# pulls, expands the unique rows to positions and hands the plan to the apply.
# Same values in the same places: the scan leaves what K `train_step` calls
# leave and what the scan without a plan (the per-position pull and an apply
# that dedups and gathers for itself: the program as it was) leaves ----------

_PB, _PF, _PROWS, _PDIM, _PK = 64, 8, 1024, 8, 3   # 512 positions a step
# name -> (distinct valid rows a batch holds, what else it holds, combiner)
_PLAN_CASES = {
    "duplicates_rung_0": (40, "", ""),
    "rung_1": (200, "", ""),
    "rung_2": (300, "", ""),
    "full_size_rung": (450, "", ""),
    "negative_ids": (150, "negative", ""),
    "ids_past_the_rows": (150, "past", ""),
    "padded_bags_combined": (200, "padding", "mean"),
}


class _BagTower(nn.Module):
    """A dense layer over the rows, flattened: (B, F, dim) as pulled, or
    (B, dim) where the spec combines a bag."""

    @nn.compact
    def __call__(self, embedded, dense_inputs):
        x = embedded["emb"].reshape(embedded["emb"].shape[0], -1)
        return nn.Dense(1)(x)[:, 0]


class _PerPositionTrainer(Trainer):
    """The packed pull as it was: the full packed row once a position, no
    plan, so `_packed_apply` calls `sparse_apply_packed_table` without one."""

    def _packed_pull(self, spec, table, ids, layout):
        from openembedding_tpu.ops.sparse import (gather_packed_rows,
                                                  packed_width)
        rows = gather_packed_rows(
            table.weights, packed_width(spec.output_dim, layout),
            ids.reshape(-1))
        return table, rows[:, :spec.output_dim].astype(spec.dtype).reshape(
            ids.shape + (spec.output_dim,)), {}, None


def _plan_batches(case):
    valid, extra, _ = _PLAN_CASES[case]
    rng = np.random.default_rng(sorted(_PLAN_CASES).index(case))
    out = []
    for _ in range(_PK):
        pool = rng.permutation(_PROWS)[:valid]
        ids = rng.permutation(np.concatenate(
            [pool, rng.choice(pool, _PB * _PF - valid)]))
        first = np.unique(ids, return_index=True)[1]
        free = np.setdiff1d(np.arange(ids.size), first)  # every row keeps one
        bad = rng.choice(free, min(120, free.size), replace=False)
        if extra == "negative":
            ids[bad] = -1 - rng.integers(0, 7, bad.size)
        elif extra == "past":
            ids[bad] = _PROWS + rng.integers(0, 3 * _PROWS, bad.size)
            ids[bad[:4]] = [_PROWS, 2**31 - 1, _PROWS + 1, _PROWS]
        elif extra == "padding":
            ids[bad] = -1
        out.append({"sparse": {"emb": ids.reshape(_PB, _PF).astype(np.int32)},
                    "dense": None,
                    "label": rng.integers(0, 2, (_PB,)).astype(np.float32)})
        assert np.unique(ids[(ids >= 0) & (ids < _PROWS)]).size == valid
    return out


_plan_programs = {}


def _plan_program(gate, combiner, sample, stacked):
    """The three programs of a (gate, combiner), traced while the caller's
    gate is patched in: the scan with the shared plan, the step, the scan as
    it was. Every case of the pair has the same shapes, so each compiles once
    a process; a case brings its own batches and fresh states (which is why
    the key is not the arguments: no `lru_cache`)."""
    if (gate, combiner) in _plan_programs:
        return _plan_programs[gate, combiner]

    def trainer(cls):
        layer = embed.Embedding(_PROWS, _PDIM, name="emb", combiner=combiner)
        return cls(embed.EmbeddingModel(_BagTower(), [layer]),
                   embed.Adagrad(learning_rate=0.1), seed=2)

    tr, old = trainer(Trainer), trainer(_PerPositionTrainer)
    assert "emb" in tr._packed_layouts(tr.init(sample))
    many = tr.jit_train_many()
    text = many.lower(tr.init(sample), stacked).as_text()
    # the pull's conditional and the apply's, or neither
    assert text.count("stablehlo.case") == (2 if gate == 0 else 0)
    _plan_programs[gate, combiner] = (tr, many, tr.jit_train_step(), old,
                                      old.jit_train_many())
    return _plan_programs[gate, combiner]


@pytest.mark.parametrize("case,gate", [(c, 0) for c in sorted(_PLAN_CASES)] + [
    ("duplicates_rung_0", None), ("negative_ids", None)])
def test_scan_with_the_shared_plan_is_the_step_loop_and_the_plan_less_scan(
        case, gate, monkeypatch):
    """gate 0: the ladder engaged, as for a table of `FAST_MEMORY_BYTES` and
    more (both conditionals traced, the case's rung taken); None: a table
    under it (no conditional, the gather at n)."""
    if gate is not None:
        monkeypatch.setattr(sparse, "FAST_MEMORY_BYTES", gate)
    valid, _, combiner = _PLAN_CASES[case]
    assert apply_ladder(_PB * _PF) == (128, 256, 384, 512)
    batches = _plan_batches(case)
    stacked = jax.tree_util.tree_map(
        lambda *xs: np.stack(xs) if xs[0] is not None else None, *batches,
        is_leaf=lambda x: x is None)
    tr, many, step, old, old_many = _plan_program(gate, combiner, batches[0],
                                                  stacked)
    planned, m_planned = many(tr.init(batches[0]), stacked)
    assert int(m_planned["apply_full_steps"]["emb"]) == \
        (_PK if gate == 0 and valid > 384 else 0)

    stepped, losses = tr.init(batches[0]), []
    for b in batches:
        stepped, m = step(stepped, b)
        losses.append(np.asarray(m["loss"]))
    plan_less, m_plan_less = old_many(old.init(batches[0]), stacked)

    for other, their_losses in ((stepped, np.stack(losses)),
                                (plan_less, np.asarray(m_plan_less["loss"]))):
        np.testing.assert_array_equal(np.asarray(m_planned["loss"]),
                                      their_losses)
        for a, b in zip(jax.tree_util.tree_leaves(planned.tables),
                        jax.tree_util.tree_leaves(other.tables)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # a table that moved: the comparison is not of two untouched states
    assert not np.array_equal(np.asarray(planned.tables["emb"].weights),
                              np.asarray(tr.init(batches[0]).tables["emb"].weights))


def test_plan_and_apply_take_positions_to_leave_out_and_multiplicities():
    """What the owner side of the exchange will call them with: a plan made
    from which positions count (0 / 1), an apply that brings the
    multiplicities: the table of the plan-less apply, bit for bit."""
    from openembedding_tpu.ops.sparse import plan_packed_rows
    rng = np.random.default_rng(5)
    n = 512
    opt = embed.Adagrad(learning_rate=0.1)
    slots = opt.init_slots(_PROWS, _PDIM)
    lay = packed_layout(_PDIM, slots)
    packed = pack_table(jnp.asarray(rng.standard_normal((_PROWS, _PDIM)),
                                    jnp.float32), slots, lay)
    ids = jnp.asarray(rng.integers(-2, _PROWS // 4, n), jnp.int32)
    pre = jnp.asarray(rng.integers(0, 4, n), jnp.int32)   # a quarter padding
    g = jnp.asarray(rng.standard_normal((n, _PDIM)), jnp.float32)

    def planned(p):
        plan = plan_packed_rows(p, ids, (pre > 0).astype(jnp.int32),
                                width=packed.shape[1])
        return sparse_apply_packed_table(opt, p, lay, _PDIM, ids, g, pre,
                                         plan=plan)
    want, load = jax.jit(lambda p: sparse_apply_packed_table(
        opt, p, lay, _PDIM, ids, g, pre))(packed)
    got, got_load = jax.jit(planned)(packed)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(got_load["apply_fill"], load["apply_fill"])
    assert not np.array_equal(np.asarray(got), np.asarray(packed))


@pytest.mark.parametrize("where", ["Trainer", "MeshTrainer"])
def test_k_step_scan_leaves_the_tables_of_the_scatter_based_dedup(
        where, monkeypatch):
    """PR 37: the dedup by sorts (`ops/dedup._run_heads`) against the bodies
    it replaced (`tests/dedup_reference.py`, patched into every call site
    while the second scan traces), and against K `train_step` calls: the
    tables bit for bit, one chip's path and the 8-device exchange's (client
    route + owner dedup)."""
    import dedup_reference

    V, steps = 4096, 4
    batches = list(synthetic_criteo(64, id_space=V, steps=steps, seed=21))
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)

    def trainer():
        model = make_deepfm(vocabulary=V, dim=9, hidden=(8,))
        opt = embed.Adagrad(learning_rate=0.05)
        if where == "Trainer":
            return Trainer(model, opt, seed=3)
        return MeshTrainer(model, opt, seed=3, mesh=make_mesh(), wire="fp32")

    def many(tr):
        state = tr.init(batches[0])
        fn = (tr.jit_train_many() if where == "Trainer"
              else tr.jit_train_many(stacked, state))
        return fn(state, stacked)

    scanned, m = many(trainer())

    tr = trainer()
    stepped, losses = tr.init(batches[0]), []
    step = (tr.jit_train_step() if where == "Trainer"
            else tr.jit_train_step(batches[0], stepped))
    for b in batches:
        stepped, sm = step(stepped, b)
        losses.append(np.asarray(sm["loss"]))

    traced = []
    for name in ("unique_with_counts", "unique_and_route"):
        def counted(*a, _f=getattr(dedup_reference, name), _n=name, **kw):
            traced.append(_n)
            return _f(*a, **kw)
        monkeypatch.setattr(dedup_reference, name, counted)
    dedup_reference.patch_reference_dedup(monkeypatch)
    referenced, rm = many(trainer())
    monkeypatch.undo()
    assert "unique_with_counts" in traced
    assert ("unique_and_route" in traced) == (where == "MeshTrainer")

    np.testing.assert_array_equal(np.asarray(m["loss"]),
                                  np.asarray(rm["loss"]))
    # (the mesh's scan and its step loop reduce the loss in another order: an
    # ulp apart on the parent too; the tables are the step loop's to a bit)
    np.testing.assert_allclose(np.asarray(m["loss"]), np.stack(losses),
                               rtol=1e-6)
    for other in (stepped, referenced):
        for a, b in zip(jax.tree_util.tree_leaves(scanned.tables),
                        jax.tree_util.tree_leaves(other.tables)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(
        np.asarray(scanned.tables["categorical"].weights),
        np.asarray(trainer().init(batches[0]).tables["categorical"].weights))
