"""Packed weights+slots layout inside `Trainer.train_many` (ops/sparse.py).

The packed form exists only inside the scan; these tests pin (a) exact
numeric parity against the split-layout step path, (b) the width gate, and
(c) that the state coming out of `train_many` is back in the split layout
(checkpoints/serving/offload never see packed arrays).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import openembedding_tpu as embed
from openembedding_tpu.data import synthetic_criteo
from openembedding_tpu.model import Trainer
from openembedding_tpu.models import make_deepfm
from openembedding_tpu.ops.sparse import (packed_layout, pack_table,
                                          sparse_apply_dense_table,
                                          sparse_apply_packed_table,
                                          unpack_table)


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


@pytest.mark.parametrize("opt_name", ["adagrad", "adam", "ftrl"])
def test_packed_apply_matches_split(opt_name):
    """One fused update through both layouts: bit-identical tables."""
    opt = {"adagrad": embed.Adagrad(learning_rate=0.1),
           "adam": embed.Adam(learning_rate=0.01),
           "ftrl": embed.Ftrl(learning_rate=0.1)}[opt_name]
    dim, rows, n = 6, 64, 40
    rng = np.random.default_rng(1)
    w = jnp.asarray(rng.standard_normal((rows, dim)), jnp.float32)
    slots = opt.init_slots(rows, dim)
    lay = packed_layout(dim, slots)
    if lay is None:
        pytest.skip(f"{opt_name}: not packable at dim {dim}")
    ids = jnp.asarray(rng.integers(-1, rows, n), jnp.int32)  # incl. invalid
    g = jnp.asarray(rng.standard_normal((n, dim)), jnp.float32)

    sw, ss = jax.jit(lambda w, s: sparse_apply_dense_table(opt, w, s, ids, g))(
        w, slots)
    packed = jax.jit(lambda w, s: sparse_apply_packed_table(
        opt, pack_table(w, s, lay), lay, dim, ids, g))(w, slots)
    pw, ps = unpack_table(packed, lay, dim, w.dtype)
    np.testing.assert_array_equal(np.asarray(sw), np.asarray(pw))
    for k in ss:
        np.testing.assert_array_equal(np.asarray(ss[k]), np.asarray(ps[k]))


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


def test_mesh_train_many_packed_matches_step_loop():
    """MeshTrainer's scan packs per shard: jit_train_many (packed, plan-reusing
    sharded apply) == sequential jit_train_step (split) on the same 8-device
    mesh — losses and final sharded tables exact."""
    from openembedding_tpu.parallel import MeshTrainer, make_mesh

    V, steps = 4096, 4
    model = make_deepfm(vocabulary=V, dim=8)
    mesh = make_mesh()
    trainer = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), mesh=mesh)
    batches = list(synthetic_criteo(64, id_space=V, steps=steps, seed=13))
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)

    state = trainer.init(batches[0])
    many = trainer.jit_train_many(stacked, state)
    sm, metrics = many(state, stacked)

    trainer2 = MeshTrainer(model, embed.Adagrad(learning_rate=0.05), mesh=mesh)
    state2 = trainer2.init(batches[0])
    step = trainer2.jit_train_step(batches[0], state2)
    losses = []
    for b in batches:
        state2, m = step(state2, b)
        losses.append(float(m["loss"]))

    np.testing.assert_allclose(np.asarray(metrics["loss"]), losses,
                               rtol=0, atol=0)
    (name, spec), = model.ps_specs().items()
    assert sm.tables[name].weights.shape[1] == spec.output_dim
    np.testing.assert_array_equal(np.asarray(sm.tables[name].weights),
                                  np.asarray(state2.tables[name].weights))
    for k, v in state2.tables[name].slots.items():
        np.testing.assert_array_equal(np.asarray(sm.tables[name].slots[k]),
                                      np.asarray(v))


def test_mesh_train_many_packed_hash(tmp_path):
    """Hash tables on the mesh pack too (probe/insert/overflow unchanged);
    checkpoint saved from the post-scan state restores identically."""
    from openembedding_tpu.embedding import Embedding
    from openembedding_tpu.model import EmbeddingModel
    from openembedding_tpu.models.ctr import LogisticRegression
    from openembedding_tpu.parallel import MeshTrainer, make_mesh

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


def test_packed_scan_compiles_one_scatter_per_table():
    """Structural pin on the packed win: the compiled train_many updates the
    table through ONE scatter into the packed (V, 20) array — never the two
    split-layout scatters ((V, 10) weights + (V, 10) accum) — and temps stay
    far below a second table copy. HLO-shape matching is deliberately narrow;
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
    split = _count_table_scatters(txt, f"{V},10")
    assert packed == 1, f"expected 1 packed-table scatter, found {packed}"
    assert split == 0, f"split-layout scatters reappeared: {split}"

    ma = compiled.memory_analysis()
    if ma is not None:  # backend-dependent
        packed_bytes = V * 20 * 4
        assert ma.temp_size_in_bytes < 3 * packed_bytes, (
            f"temps {ma.temp_size_in_bytes} suggest an extra table copy "
            f"inside the scan (packed table is {packed_bytes})")


def test_packed_scan_dim64_split_first_order_one_scatter_each():
    """The dim-64 benchmark configuration: split
    first-order auto-engages at lane-multiple dims, so train_many packs BOTH
    tables — categorical 64+64 -> (V, 128) lane-exact, first_order 1+1 ->
    (V, 2) sublane — and each updates through ONE packed scatter with no
    split-shape scatters left. The on-chip HBM claim (no 128-lane-padded temp
    copy of the table at width 128) needs a chip run (`bench.py` dim64);
    this pins the program STRUCTURE on any backend."""
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
    assert cat == 1, f"expected 1 packed categorical scatter, found {cat}"
    assert fo == 1, f"expected 1 packed first-order scatter, found {fo}"
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
