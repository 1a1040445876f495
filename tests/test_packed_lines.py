"""The LINE FORM of a narrow packed table (`ops/sparse.py` "FOUR ROWS A LANE
LINE"): a packed row of 17 to 32 columns padded to 32, four rows a 128-lane
line (line l of L holds the rows l, l + L, l + 2L, l + 3L), inside
`train_many`'s scan alone. Pinned here: pack / unpack are
inverses at every row count the rule lets in (whole blocks of lines: no
padding row); the rule that chooses the form reads shapes alone; the apply over lines leaves the row form's table bit for bit, with 1
to 4 updated rows a line, at every rung, with and without the pull's plan;
the trainers' scans leave the step loop's tables; the row-DMA kernel takes
the merged lines (ascending targets, a run of equal targets carrying equal
lines).
"""

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

import openembedding_tpu as embed
from openembedding_tpu.model import Trainer
from openembedding_tpu.ops import sparse
from openembedding_tpu.ops.sparse import (apply_ladder, in_lines, pack_table,
                                          packed_layout, packed_rows,
                                          packed_width, plan_packed_rows,
                                          sparse_apply_packed_table,
                                          takes_lines, unpack_table)

import dedup_reference
from apply_reference import (ROUNDS_UNDER_JIT, SLOTTED_OPTS,
                             assert_same_table, warm_table)

DIM = 10                       # Adagrad: 10 + 10 = the benchmark's width 20


@pytest.fixture
def narrow_tables_take_lines(monkeypatch):
    """Small tables as tables of `FAST_MEMORY_BYTES` and more: the ladder and
    the line form engage (as `tests/test_packed_layout.py` does for the
    ladder)."""
    monkeypatch.setattr(sparse, "FAST_MEMORY_BYTES", 0)


@pytest.mark.parametrize("rows", [512, 1024, 1536, 4096])
def test_pack_and_unpack_are_inverses_whatever_the_row_count(
        rows, narrow_tables_take_lines):
    rng = np.random.default_rng(rows)
    w = jnp.asarray(rng.standard_normal((rows, DIM)), jnp.float32)
    slots = {"accum": jnp.asarray(rng.random((rows, DIM)), jnp.float32)}
    lay = packed_layout(DIM, slots)
    packed = pack_table(w, slots, lay)
    assert packed.shape == (rows // 4, 128) and in_lines(packed, 20)
    assert packed_rows(packed, 20) == rows
    # row r at lanes 32 * (r // L) of line r % L
    as_rows = np.asarray(packed).reshape(-1, 4, 32).transpose(1, 0, 2).reshape(
        -1, 32)
    np.testing.assert_array_equal(as_rows[:, :DIM], np.asarray(w))
    assert not as_rows[:, 20:].any()
    w2, s2 = unpack_table(packed, lay, DIM, jnp.float32)
    np.testing.assert_array_equal(np.asarray(w2), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(s2["accum"]),
                                  np.asarray(slots["accum"]))


# (weight columns, slot columns, dtype, rows, PACKED_MAX_BYTES) -> the form:
# None = not packed at all (`packed_layout`)
_BIG = 1 << 21   # x 17 columns and more x 4 bytes: over FAST_MEMORY_BYTES
_RULE = {
    "width_2": (1, 1, jnp.float32, 1 << 25, None, "rows"),
    "width_16": (8, 8, jnp.float32, 1 << 22, None, "rows"),
    "width_17": (9, 8, jnp.float32, _BIG, None, "lines"),
    "width_20": (10, 10, jnp.float32, _BIG, None, "lines"),
    "width_32": (16, 16, jnp.float32, _BIG, None, "lines"),
    "width_33": (17, 16, jnp.float32, _BIG, None, None),
    "width_128": (64, 64, jnp.float32, _BIG, None, "rows"),
    "width_256": (128, 128, jnp.float32, 1 << 20, None, "rows"),
    "bf16_weights": (10, 10, jnp.bfloat16, _BIG, None, None),
    "under_fast_memory": (10, 10, jnp.float32, 1 << 20, None, "rows"),
    # whole blocks of lines alone: no padding row that an id could reach,
    # and no table the pack's and the unpack's kernels do not take
    "rows_not_a_multiple_of_4": (10, 10, jnp.float32, _BIG + 3, None, "rows"),
    "lines_no_block_divides": (10, 10, jnp.float32, _BIG + 4, None, "rows"),
    "lines_the_smallest_block_divides": (10, 10, jnp.float32, _BIG + 512,
                                         None, "lines"),
    # as rows it passes the limit, padded to lines it does not
    "padded_bytes_over_the_limit": (10, 10, jnp.float32, _BIG,
                                    _BIG * 24 * 4, "rows"),
    "the_benchmark_table": (10, 10, jnp.float32, 1 << 25, None, "lines"),
}


@pytest.mark.parametrize("case", sorted(_RULE))
def test_the_form_is_chosen_from_shapes_alone(case, monkeypatch):
    dim, slot, dtype, rows, limit, form = _RULE[case]
    if limit is not None:
        monkeypatch.setattr(sparse, "PACKED_MAX_BYTES", limit)
    like = jax.ShapeDtypeStruct
    slots = {"accum": like((rows, slot), jnp.float32)}
    lay = packed_layout(dim, slots, dtype)
    assert (lay is None) == (form is None)
    if lay is None:
        return
    width = packed_width(dim, lay)
    assert takes_lines(rows, width) == (form == "lines")
    packed = jax.eval_shape(lambda w, s: pack_table(w, s, lay),
                            like((rows, dim), dtype), slots)
    assert packed.shape == ((rows // 4, 128) if form == "lines"
                            else (rows, width))
    assert in_lines(packed, width) == (form == "lines")
    assert packed_rows(packed, width) == rows


def test_packed_tables_are_counted_by_form(narrow_tables_take_lines):
    from openembedding_tpu.utils import metrics
    count = lambda f: metrics.report().get(
        'sparse.packed_tables{form="%s"}' % f) or 0.0
    before = {f: count(f) for f in ("lines", "rows")}
    for dim in (10, 8):   # width 20: lines; width 16: rows
        pack_table(jnp.zeros((512, dim)), {"a": jnp.zeros((512, dim))},
                   (("a", dim),))
    assert {f: count(f) - before[f] for f in before} == {"lines": 1, "rows": 1}


@pytest.mark.parametrize("reader", ["plan", "hash_pull", "shard_pull"])
def test_a_packed_table_read_without_its_layout_raises(
        reader, narrow_tables_take_lines):
    """(L, 128) lines cannot be told from L rows of 128 by the shape, so no
    reader of a packed array defaults the layout's width: left out it is an
    error while tracing, never wrong rows."""
    from openembedding_tpu.parallel import sharded
    from openembedding_tpu.tables.hash_table import hash_lookup_train
    w = jnp.zeros((512, DIM), jnp.float32)
    packed = pack_table(w, {"accum": w}, (("accum", DIM),))
    ids = jnp.arange(8, dtype=jnp.int32)
    if reader == "plan":
        with pytest.raises(TypeError, match="width"):
            plan_packed_rows(packed, ids)
        return
    layer = (embed.Embedding(-1, DIM, name="emb", capacity=512)
             if reader == "hash_pull" else embed.Embedding(512, DIM, name="emb"))
    spec = embed.EmbeddingModel(_Tower(), [layer]).specs["emb"]
    with pytest.raises(ValueError, match="layout"):
        if reader == "hash_pull":
            state = embed.embedding.init_table_state(
                spec, embed.Adagrad(learning_rate=0.1))
            hash_lookup_train(state.replace(weights=packed), ids, out_dim=DIM)
        else:
            sharded._weight_rows(spec, packed, ids, None)


# ---------------------------------------------------------------------------
# the apply over lines == the apply over rows == the scatter-based reference
# ---------------------------------------------------------------------------

_R, _N = 4096, 2304            # rows; slots: over two kernel blocks
_L = _R // 4                   # 1,024 lines: row r in line r % L, place r // L
assert apply_ladder(_N) == (640, 1152, 1792, 2304)


def _runs(lines, rows_a_line):
    """Row ids: the first `rows_a_line` places of each of `lines` (line-major
    order: what the sorted unique buffer holds)."""
    return (np.asarray(lines)[:, None]
            + _L * np.arange(rows_a_line)[None, :]).reshape(-1)


def _ids_case(case):
    """-> (ids (N,), pre_counts (N,) or None): what the unique buffer holds is
    the case's; the rest of the N positions are duplicates of it."""
    rng = np.random.default_rng(sorted(_ID_CASES).index(case))
    pre = None
    if case in ("one_a_line", "two_a_line", "three_a_line", "four_a_line"):
        k = 1 + ("one_a_line", "two_a_line", "three_a_line",
                 "four_a_line").index(case)
        uniq = _runs(3 + np.arange(400 // k), k)           # rung 0
    elif case == "mixed_rung_1":
        uniq = np.sort(rng.choice(_R, 1000, replace=False))
    elif case == "mixed_rung_2":
        uniq = np.sort(rng.choice(_R, 1500, replace=False))
    elif case == "full_size_rung":
        uniq = np.sort(rng.choice(_R, 2200, replace=False))
    elif case == "a_run_across_a_rungs_edge":
        # 638 single rows, then a line of four: slots 638..641 around W = 640
        uniq = np.concatenate([np.arange(638), _runs([900], 4)])
    elif case == "a_run_across_a_kernel_blocks_edge":
        # slots 1022..1025 hold one line: around the row-DMA kernel's 1,024
        uniq = np.concatenate([np.arange(1022), _runs([1022], 4),
                               _runs([1023], 3)])  # the last line
    elif case == "negative_and_out_of_range":
        uniq = np.sort(rng.choice(_R, 300, replace=False))
    elif case == "pre_counts_zero":
        uniq = np.sort(rng.choice(_R, 700, replace=False))
    ids = np.concatenate([uniq, rng.choice(uniq, _N - uniq.size)])
    if case == "negative_and_out_of_range":
        bad = np.arange(uniq.size, _N)[::3]     # duplicates only
        # R itself among them: the first id out of range, in either form
        ids[bad] = rng.choice([-1, -7, _R, _R + 1, 2**31 - 1, 3 * _R],
                              bad.size)
    if case == "pre_counts_zero":
        pre = rng.integers(0, 3, _N).astype(np.int32)      # a third left out
    order = rng.permutation(_N)
    return ids[order].astype(np.int32), None if pre is None else pre[order]


_ID_CASES = ("a_run_across_a_kernel_blocks_edge", "a_run_across_a_rungs_edge",
             "four_a_line", "full_size_rung", "mixed_rung_1", "mixed_rung_2",
             "negative_and_out_of_range", "one_a_line", "pre_counts_zero",
             "three_a_line", "two_a_line")
_programs = {}


def _apply_program(form, planned, monkeypatch):
    """One jitted apply a (form, with a plan or without): every case has the
    same shapes, so each compiles once a process."""
    if (form, planned) in _programs:
        return _programs[form, planned]
    opt = embed.Adagrad(learning_rate=0.1)
    lay = (("accum", DIM),)

    def run(w, acc, ids, pre, g):
        if form == "lines":
            packed = pack_table(w, {"accum": acc}, lay)
            assert in_lines(packed, 20)
        else:  # the row form, whatever the rule says
            packed = jnp.concatenate([w, acc], axis=1)
        plan = plan_packed_rows(packed, ids, None if pre is None else
                                (pre > 0).astype(jnp.int32),
                                width=20) if planned else None
        out, load = sparse_apply_packed_table(opt, packed, lay, DIM, ids, g,
                                              pre, plan=plan)
        pulled = None if plan is None else plan.rows[:, :DIM][plan.uniq.inverse]
        return unpack_table(out, lay, DIM, jnp.float32), load, pulled

    with monkeypatch.context() as m:
        m.setattr(sparse, "FAST_MEMORY_BYTES", 0)
        if form == "reference":
            dedup_reference.patch_reference_dedup(m)
        fns = {}
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.standard_normal((_R, DIM)), jnp.float32)
        acc = jnp.full((_R, DIM), 0.1, jnp.float32)
        g = jnp.asarray(rng.standard_normal((_N, DIM)), jnp.float32)
        ids = jnp.zeros((_N,), jnp.int32)
        for with_pre in (False, True):  # trace both signatures while patched
            f = fns[with_pre] = jax.jit(run)
            f(w, acc, ids, ids if with_pre else None, g)
    _programs[form, planned] = (fns, w, acc, g)
    return _programs[form, planned]


@pytest.mark.parametrize("planned", [True, False], ids=["plan", "no_plan"])
@pytest.mark.parametrize("case", _ID_CASES)
def test_apply_over_lines_leaves_the_row_forms_table(case, planned,
                                                     monkeypatch):
    ids, pre = _ids_case(case)
    got = {}
    for form in ("lines", "rows", "reference"):
        fns, w, acc, g = _apply_program(form, planned and form != "reference",
                                        monkeypatch)
        got[form] = jax.tree_util.tree_map(
            np.asarray, fns[pre is not None](w, acc, ids, pre, g))
    (w_l, s_l), load_l, pulled_l = got["lines"]
    for form in ("rows", "reference"):
        (w_o, s_o), load_o, pulled_o = got[form]
        np.testing.assert_array_equal(w_l, w_o)
        np.testing.assert_array_equal(s_l["accum"], s_o["accum"])
        assert load_l["apply_fill"] == load_o["apply_fill"]
        assert load_l["apply_full_steps"] == load_o["apply_full_steps"]
        if pulled_o is not None:   # the pull's rows, position by position
            np.testing.assert_array_equal(pulled_l, pulled_o)
    assert (w_l != np.asarray(w)).any()
    assert load_l["apply_full_steps"] == (case == "full_size_rung")
    # the counter: of the valid unique rows, those with a mate on their line
    ok = (ids >= 0) & (ids < _R) & (True if pre is None else pre > 0)
    rows = np.unique(ids[ok])
    a_line = np.bincount(rows % _L)
    np.testing.assert_allclose(load_l["line_mates"],
                               (a_line[rows % _L] > 1).mean(), rtol=1e-6)
    assert "line_mates" not in got["rows"][1]   # XLA's scatter: not counted
    mates = {"one_a_line": 0.0, "two_a_line": 1.0, "four_a_line": 1.0}
    if case in mates:
        assert load_l["line_mates"] == mates[case]


# every optimizer that has slots, each at a dim that puts its packed width in
# the rule's range, both ends of it included (17 and 32 columns)
_OPT_DIMS = {"sgd": 9, "adagrad": 10, "adadelta": 8, "adam": 10, "adamax": 10,
             "ftrl": 10, "rmsprop": 8, "test": 16}


@pytest.mark.parametrize("opt", SLOTTED_OPTS, ids=lambda o: o.category)
def test_every_optimizers_slots_go_through_the_line_form(
        opt, narrow_tables_take_lines, monkeypatch):
    """One fused update of a table held four rows a line against the same
    table in the row form and in the split layout: every slot of every
    optimizer is packed, picked, merged and unpacked at the columns its
    layout gives it (the cases above are Adagrad's one `accum`). As compiled
    programs the two packed forms agree bit for bit and the split layout as
    `test_packed_apply_matches_split` says; op by op all three do."""
    dim, rows, n = _OPT_DIMS[opt.category], 1024, 300
    assert apply_ladder(n) == (128, 256, 300)
    w, slots, ids, g = warm_table(opt, rows, dim, n,
                                  np.random.default_rng(dim))
    lay = packed_layout(dim, slots)
    width = packed_width(dim, lay)
    assert 16 < width <= 32 and takes_lines(rows, width)

    def split(w, s):
        return sparse.sparse_apply_dense_table(opt, w, s, ids, g)

    def through(lined, run):
        def apply(w, s):
            packed = pack_table(w, s, lay)
            assert in_lines(packed, width) == lined
            out, load = sparse_apply_packed_table(opt, packed, lay, dim, ids, g)
            assert ("line_mates" in load) == lined
            return unpack_table(out, lay, dim, jnp.float32)
        with monkeypatch.context() as m:
            if not lined:
                m.setattr(sparse, "takes_lines", lambda r, w: False)
            return run(apply)(w, slots)

    want = jax.jit(split)(w, slots)
    assert (np.asarray(want[0]) != np.asarray(w)).any()
    lines, row_form = through(True, jax.jit), through(False, jax.jit)
    assert_same_table(row_form, lines)
    assert_same_table(want, lines, exact=opt.category not in ROUNDS_UNDER_JIT)
    with jax.disable_jit():
        want = split(w, slots)
        for lined in (True, False):
            assert_same_table(want, through(lined, lambda f: f))


# ---------------------------------------------------------------------------
# the row-DMA kernel under the run treatment chosen: every slot of a run
# writes the SAME merged line (targets ascending, not duplicate-free)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,block,heads", [
    (40, 16, [0, 1, 3, 6, 10, 11, 15, 16, 17, 30]),  # runs over blocks' edges
    (64, 16, list(range(0, 64, 4))),                 # every run of four
    (70, 32, [0, 1, 2, 40]),                         # padding blocks after
    (2050, 1024, [0, 1021, 1025, 2047]),             # the kernel's own size
], ids=["ragged_runs", "all_fours", "then_padding", "blocks_of_1024"])
def test_dma_scatter_takes_runs_of_equal_lines(n, block, heads):
    from openembedding_tpu.ops import pallas_scatter
    from openembedding_tpu.ops.sparse import scatter_rows
    rng = np.random.default_rng(n)
    n_lines, n_valid = 3000, max(heads) + 3
    head = np.zeros(n, bool)
    head[heads] = True
    run = np.cumsum(head) - 1                   # slots before heads[0]: none
    line_of_run = np.sort(rng.choice(n_lines, len(heads), replace=False))
    target = np.where(np.arange(n) < n_valid, line_of_run[run],
                      n_lines + np.arange(n) // 4).astype(np.int32)
    per_run = rng.standard_normal((len(heads), 128)).astype(np.float32)
    lines = np.where((np.arange(n) < n_valid)[:, None], per_run[run],
                     rng.standard_normal((n, 128))).astype(np.float32)
    table = rng.standard_normal((n_lines, 128)).astype(np.float32)
    want = table.copy()
    want[line_of_run] = per_run
    got = jax.jit(lambda *a: pallas_scatter.scatter_rows(
        *a, block=block, interpret=True))(table, target, lines)
    np.testing.assert_array_equal(np.asarray(got), want)
    # what the other lowerings run: XLA's scatter, told the truth
    np.testing.assert_array_equal(np.asarray(scatter_rows(
        jnp.asarray(table), target, lines, sorted_unique=True, runs=True)),
        want)


# ---------------------------------------------------------------------------
# the trainers' scans in the line form == the step loop (split layout)
# ---------------------------------------------------------------------------

_B, _F, _V, _K = 256, 8, 2048, 3    # 2,048 positions a step; on four shards
#                                     512 rows each: 128 lines, one block


class _Tower(nn.Module):
    @nn.compact
    def __call__(self, embedded, dense_inputs):
        x = embedded["emb"].reshape(embedded["emb"].shape[0], -1)
        return nn.Dense(1)(x)[:, 0]


def _batches(seed, clustered=True):
    """Ids that share lines (a Zipf head in every quarter of the rows: line l
    of L holds the rows l, l + L, ...), with negative and out-of-range ids
    among them."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(_K):
        # + k * 512: the same line of a 2,048-row table (512 lines) and of
        # its four shards (row r is row r // 4 of shard r % 4: 128 lines)
        ids = (rng.zipf(1.2, (_B, _F)) + rng.integers(0, 4, (_B, _F)) * 512
               ) % _V
        if not clustered:
            ids = (ids * 7919) % _V
        bad = rng.random((_B, _F))
        ids = np.where(bad < 0.05, -1 - rng.integers(0, 4, ids.shape), ids)
        ids = np.where(bad > 0.95, _V + 4 + rng.integers(0, _V, ids.shape), ids)
        out.append({"sparse": {"emb": ids.astype(np.int32)}, "dense": None,
                    "label": rng.integers(0, 2, (_B,)).astype(np.float32)})
    return out


def _stack(batches):
    return jax.tree_util.tree_map(
        lambda *xs: np.stack(xs) if xs[0] is not None else None, *batches,
        is_leaf=lambda x: x is None)


def _same_tables(a, b, ulps=0):
    for x, y in zip(jax.tree_util.tree_leaves(a.tables),
                    jax.tree_util.tree_leaves(b.tables)):
        assert x.dtype == y.dtype and x.shape == y.shape
        if ulps and x.dtype == jnp.float32:
            np.testing.assert_array_max_ulp(np.asarray(x), np.asarray(y), ulps)
        else:
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _forms_counted():
    from openembedding_tpu.utils import metrics
    return metrics.report().get('sparse.packed_tables{form="lines"}') or 0.0


@pytest.mark.parametrize("table", ["array", "hash"])
def test_train_many_in_lines_is_the_step_loop(table, narrow_tables_take_lines):
    """`Trainer.train_many` (an array table: the shared plan; a hash table:
    the packed apply with no plan, the pull per position) against K
    `train_step` calls, bit for bit, and the window's `line_mates`."""
    batches = _batches(1)
    if table == "hash":
        for b in batches:   # a hash table takes any id: keep them valid
            b["sparse"]["emb"] = np.abs(b["sparse"]["emb"]) % _V

    def trainer():
        layer = (embed.Embedding(_V, DIM, name="emb") if table == "array" else
                 embed.Embedding(-1, DIM, name="emb", capacity=4096))
        return Trainer(embed.EmbeddingModel(_Tower(), [layer]),
                       embed.Adagrad(learning_rate=0.1), seed=2)

    tr, before = trainer(), _forms_counted()
    scanned, m = tr.jit_train_many()(tr.init(batches[0]), _stack(batches))
    assert _forms_counted() == before + 1
    stepped, losses, step = tr.init(batches[0]), [], tr.jit_train_step()
    for b in batches:
        stepped, sm = step(stepped, b)
        losses.append(np.asarray(sm["loss"]))
    np.testing.assert_array_equal(np.asarray(m["loss"]), np.stack(losses))
    # the hash table's scan sorts its slots another way, the CPU compiler
    # fuses the gradients' last multiply into the duplicates' sum in one
    # program and not in the other, and a contracted multiply-add rounds
    # once: an ulp on a few weights (equal under
    # `--xla_cpu_max_isa=SSE4_2`, which has no such instruction; the sums'
    # ORDER is pinned bit for bit by the apply's own cases above, by the
    # array table here and by the mesh's hash table below)
    _same_tables(scanned, stepped, ulps=0 if table == "array" else 2)
    assert 0.0 < float(m["line_mates"]["emb"]) <= 1.0
    tr.record_window_stats(m)
    from openembedding_tpu.utils import metrics
    assert metrics.report()['sparse.line_mates{table="emb"}'] == \
        pytest.approx(float(m["line_mates"]["emb"]))


@pytest.mark.parametrize("case", ["four_shards", "four_shards_full_size_step",
                                  "four_shards_pipelined", "four_shards_hash"])
def test_mesh_train_many_in_lines_is_the_step_loop(case,
                                                   narrow_tables_take_lines):
    """`MeshTrainer.train_many` on the CPU mesh with every shard in the line
    form: the owner's plan and apply over lines; a step that does not fit the
    owner's working size (no plan: the apply gathers its lines itself); the
    pipelined scan (rows served a step early, per slot, from lines); a hash
    table."""
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    mesh = make_mesh(jax.devices()[:4])
    batches = _batches(2, clustered=case != "four_shards_full_size_step")
    if case == "four_shards_full_size_step":
        for b in batches:   # every id shard 0's: it receives over W
            b["sparse"]["emb"] = np.where(
                b["sparse"]["emb"] >= 0, b["sparse"]["emb"] // 4 * 4,
                b["sparse"]["emb"]).astype(np.int32)
    if case == "four_shards_hash":
        for b in batches:
            b["sparse"]["emb"] = np.abs(b["sparse"]["emb"]) % _V

    def trainer():
        layer = (embed.Embedding(-1, DIM, name="emb", capacity=4096)
                 if case == "four_shards_hash" else
                 embed.Embedding(_V, DIM, name="emb"))
        return MeshTrainer(embed.EmbeddingModel(_Tower(), [layer]),
                           embed.Adagrad(learning_rate=0.1), seed=2, mesh=mesh,
                           wire="fp32",
                           pipeline_steps=case == "four_shards_pipelined")

    tr, stacked, before = trainer(), _stack(batches), _forms_counted()
    state = tr.init(batches[0])
    scanned, m = tr.jit_train_many(stacked, state)(state, stacked)
    assert _forms_counted() == before + 1
    if case == "four_shards_full_size_step":
        assert int(m["owner_full_steps"]["emb"]) == _K
    tr2 = trainer()
    stepped, losses = tr2.init(batches[0]), []
    step = tr2.jit_train_step(batches[0], stepped)
    for b in batches:
        stepped, sm = step(stepped, b)
        losses.append(np.asarray(sm["loss"]))
    np.testing.assert_array_equal(np.asarray(m["loss"]), np.stack(losses))
    _same_tables(scanned, stepped)
    assert 0.0 <= float(m["line_mates"]["emb"]) <= 1.0


@pytest.mark.parametrize("rows", [_V, 1536])
def test_train_many_in_lines_through_the_kernels(rows, narrow_tables_take_lines,
                                                 monkeypatch):
    """With the kernels in the TPU lowering's place (under the interpreter)
    the scan's table is the plain path's bit for bit: ONE row-DMA kernel a
    trace writes the (rows / 4, 128) lines, and the pack and the unpack are
    `ops/pallas_lines.py`'s (2,048 rows: one block of 512 lines; 1,536: three
    of 128), as for every table the rule lets into the form."""
    from openembedding_tpu.ops import pallas_lines, pallas_scatter
    batches = _batches(3)
    for b in batches:
        b["sparse"]["emb"] = np.where(b["sparse"]["emb"] >= rows, -1,
                                      b["sparse"]["emb"]).astype(np.int32)

    def scan():
        tr = Trainer(embed.EmbeddingModel(
            _Tower(), [embed.Embedding(rows, DIM, name="emb")]),
            embed.Adagrad(learning_rate=0.1), seed=2)
        return tr.jit_train_many()(tr.init(batches[0]), _stack(batches))[0]

    plain, kernels = scan(), []

    def on_tpu(*args, tpu, default):
        fn = getattr(tpu, "func", tpu)
        if fn not in (pallas_scatter.scatter_rows, pallas_lines.pack_lines,
                      pallas_lines.unpack_lines):
            return tpu(*args)   # another kernel's entry: as it was
        kernels.append((fn.__name__, args[0].shape))
        return tpu(*args, interpret=True)

    monkeypatch.setattr(jax.lax, "platform_dependent", on_tpu)
    forced = scan()
    n = rows // 4
    assert kernels == [("pack_lines", (10, rows)), ("scatter_rows", (n, 128)),
                       ("unpack_lines", (n, 128))]
    _same_tables(plain, forced)


@pytest.mark.parametrize("lines,columns", [(128, (10, 10)), (384, (9, 8)),
                                           (2048, (16, 16)), (4096, (8, 8, 8))])
def test_line_kernels_move_the_data_as_the_plain_pack_does(lines, columns):
    """`ops/pallas_lines.py` under the interpreter against the layout's
    definition: row r at lanes 32 * (r // L) of line r % L, each array's
    columns after the one before, zeros past the width; the unpack its
    inverse, a quarter of the rows an output."""
    from openembedding_tpu.ops import pallas_lines
    rng = np.random.default_rng(lines)
    arrays = [rng.standard_normal((c, 4 * lines)).astype(np.float32)
              for c in columns]
    offsets = tuple(int(x) for x in np.cumsum((0,) + columns[:-1]))
    want = np.zeros((lines, 128), np.float32)
    for a, off in zip(arrays, offsets):
        for k in range(4):
            want[:, 32 * k + off:32 * k + off + a.shape[0]] = \
                a[:, k * lines:(k + 1) * lines].T
    got = pallas_lines.pack_lines(*map(jnp.asarray, arrays), offsets=offsets,
                                  interpret=True)
    np.testing.assert_array_equal(np.asarray(got), want)
    back = pallas_lines.unpack_lines(got, columns=columns, offsets=offsets,
                                     interpret=True)
    for j, a in enumerate(arrays):
        np.testing.assert_array_equal(np.concatenate(
            [np.asarray(q) for q in back[4 * j:4 * j + 4]], axis=1), a)
    assert pallas_lines.block_for(lines) == min(lines & -lines, 2048)
    assert pallas_lines.block_for(257) == 0
