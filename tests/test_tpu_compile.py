"""Compiles for a DESCRIBED v5e (no chip is attached or needed): what the TPU
compiler makes of the hot path at the benchmark's real shapes. A compile is
not a run: these pin program structure (copies, temporaries), never a time.

The apply's choice of a working size (`ops/sparse.py` "WHAT THE APPLY WORKS
OVER") must not cost a table: PR 29's chip probe measured a plain `lax.switch`
over the four rungs at +9.8 ms a step, a whole-table copy in every branch but
the first and the last, which `_over_unique_prefix`'s `settle` barrier cures.
Since PR 35 the one-chip scan gathers the step's unique packed rows once,
under a conditional of its own before the pull ("ONE DEDUP AND ONE TABLE
GATHER A STEP"): neither conditional may copy the table, and the table is
gathered from in the pull's branches alone, never once a position.

The client's side of the exchange builds and reads its buckets by S block
copies (`parallel/sharded.py` "WHAT THE CLIENT SENDS"): the four-chip cell's
scan, traced for the four described chips at its real shapes, holds no
scatter and no gather over S x capacity rows but in the owner's full-size
branch, and still three all-to-alls a step.

The attention core is one fused kernel on a TPU lowering
(`ops/flash_attention.py`, behind `models/nemotron_h.blockwise_causal_
attention`): at the language-model cells' shapes `value_and_grad` through the
entry compiles to custom calls under the `attn.core` stage and to no f32
array of a score block's size; a shape the tiling refuses compiles to the
plain body.

The Solar-Open2 cell's scan (`models/solar_open2.py`: 966.7M parameters, 7.2
GiB of state) fits the chip only because its routed layer GATHERS a block's
weights: the one-hot pick makes the compiler keep the experts' weights,
gradients and accumulators a second time, padded from 10 experts to 16
(20.7 of 15.75 GiB). The whole scan is compiled here at the cell's real sizes.

The Ouro cell's walk (`models/ouro.py`) is ONE scanned body with the head and
its cross-entropy inside: compiled here at the cell's widths with 2 of its 8
layers (the whole cell takes 61-108 s in the sandbox, over a tier-1 case's
room; PERF.md 4 has its numbers, made by hand): three loops whatever the
passes, the fused core in the body, and no exit's logits stacked over the
passes.
"""

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import openembedding_tpu as embed
from openembedding_tpu.ops.sparse import (FAST_MEMORY_BYTES, apply_ladder,
                                          sparse_apply_packed_table)
from openembedding_tpu.utils import guards

N = 4096 * 26  # the benchmark's positions a step


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps libtpu from loading
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    # such a compile cannot be read back from the persistent cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _row_dma_calls(text, table):
    """The stage paths of the custom calls that hand back an array of the
    shape `table` (a regex): `ops/pallas_scatter.py`'s kernel, in place."""
    return [re.search(r'op_name="([^"]*)"', line).group(1) for line in
            re.findall(rf"= {table}\S* custom-call\(([^\n]*)", text)
            if "tpu_custom_call" in line]


@pytest.mark.parametrize("rows,dim,form,n", [
    (1 << 25, 10, "rows", N), (1 << 25, 10, "lines", N), (1 << 22, 64, "rows", N),
    (1 << 22, 1, "rows", N), (16384, 2688, "rows", 8192)],
    ids=["dim9_2e25x20_as_rows", "dim9_2e23x128_lines", "dim64_2e22x128",
         "dim64_first_order_2e22x2", "token_table_16384x5376"])
def test_apply_ladder_costs_no_table_copy_on_the_tpu(one_chip, rows, dim, form, n):
    """A 2-step scan of the packed apply at the benchmark's table shapes:
    one conditional, the table updated in place in EVERY branch. The 32 MiB
    first-order table is under `FAST_MEMORY_BYTES`: no conditional, and the
    compiler keeps it in fast memory (`S(1)`) as it did (on the chip that
    scatter read 4.2 ms there and 6.1 ms through a conditional, PR 29).
    What writes the rows is the shape's choice (`ops.sparse.scatter_rows`):
    a table of one lane line a row takes the row-DMA kernel, ONE custom call
    under `sparse.apply` after the switch (the rungs hand it their new rows
    padded to n) and no scatter of XLA's: the 2^22 x 128 table, and the dim-9
    table in the form its scan holds it in (`ops.sparse.takes_lines`: 2^23
    lines of four rows, the apply gathering, merging and writing LINES). The
    same dim-9 table handed over as 2^25 rows of 20, the first-order table
    and a language model's token table (a row of 42 lane lines) compile to
    XLA's scatter and to no custom call, the program they had."""
    from openembedding_tpu.ops.sparse import pack_table, takes_row_dmas
    opt = embed.Adagrad(learning_rate=0.05)
    layout = (("accum", dim),)

    def many(packed, ids, grads):
        def body(p, xs):
            return sparse_apply_packed_table(opt, p, layout, dim, *xs)[0], None
        return jax.lax.scan(body, packed, (ids, grads))[0]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    like = jax.ShapeDtypeStruct((rows, dim), jnp.float32)
    lines = jax.eval_shape(lambda w, a: pack_table(w, {"accum": a}, layout),
                           like, like).shape
    assert lines == ((rows // 4, 128) if dim == 10 else (rows, 2 * dim))
    shape = lines if form == "lines" else (rows, 2 * dim)
    compiled = jax.jit(many, donate_argnums=(0,)).lower(
        arg(shape, jnp.float32), arg((2, n), jnp.int32),
        arg((2, n, dim), jnp.float32)).compile()
    text = compiled.as_text()
    table = rf"f32\[{shape[0]},{shape[1]}\]"
    scatters = re.findall(rf"= {table}(\S*) fusion\([^\n]*/scatter", text)
    kernels = _row_dma_calls(text, table)
    if rows * 2 * dim * 4 < FAST_MEMORY_BYTES:
        assert " conditional(" not in text and "tpu_custom_call" not in text
        assert len(scatters) == 1 and "S(1)" in scatters[0]
        return
    assert len(apply_ladder(n)) == 4 and text.count(" conditional(") == 1
    if not takes_row_dmas(arg(shape, jnp.float32)):
        assert len(scatters) == 4 and "tpu_custom_call" not in text
    else:  # ONE kernel, after the switch: the rungs hand it their rows
        assert not scatters and len(kernels) == 1, (scatters, kernels)
        # one `cond` in its path, `platform_dependent`'s own: not the switch's
        assert "sparse.apply/" in kernels[0] and kernels[0].count("cond/") == 1
    copies = re.findall(rf"= {table}\S* (?:copy|copy-start)\(", text)
    assert not copies, f"{len(copies)} table-sized copies in the program"
    if n == N:
        assert compiled.memory_analysis().temp_size_in_bytes < \
            shape[0] * shape[1] * 4 // 8


@pytest.mark.parametrize("width,dtype,taken", [
    (128, jnp.float32, True), (128, jnp.int32, True), (256, jnp.float32, False),
    (8192, jnp.float32, False), (128, jnp.bfloat16, False)])
def test_row_dma_kernel_compiles_where_the_rule_sends_it_and_nowhere_else(
        one_chip, width, dtype, taken):
    """`ops.sparse.takes_row_dmas` is what Mosaic takes: a one-row slice of
    the tiled HBM array is a DMA's end at one lane line of 4-byte elements
    alone; a wider row or a 2-byte one is refused (should a later compiler
    take them, this fails and the rule can widen)."""
    from openembedding_tpu.ops import pallas_scatter
    from openembedding_tpu.ops.sparse import takes_row_dmas

    def arg(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    assert takes_row_dmas(arg((4096, width), dtype)) == taken
    lowered = jax.jit(pallas_scatter.scatter_rows, donate_argnums=(0,)).lower(
        arg((4096, width), dtype), arg((2048,), jnp.int32),
        arg((2048, width), dtype))
    if taken:
        assert "tpu_custom_call" in lowered.compile().as_text()
    else:
        with pytest.raises(Exception, match="aligned to tiling"):
            lowered.compile()


@pytest.mark.parametrize("columns", [(10, 10), (16, 16), (9, 8), (8, 8, 8)])
def test_line_kernels_compile_for_the_tpu(one_chip, columns):
    """`ops/pallas_lines.py` at the dim-9 cells' 2^23 lines: the pack (every
    array's four quarter blocks of (columns, 2048) stacked in VMEM, 32
    sublanes a quarter, one transpose) and the unpack compile for the
    described chip, whatever sublane an array starts at, with no temporary
    beside what they return."""
    from openembedding_tpu.ops import pallas_lines
    lines = 1 << 23
    offsets = tuple(int(x) for x in np.cumsum((0,) + columns[:-1]))

    def arg(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    pack = jax.jit(lambda *a: pallas_lines.pack_lines(
        *a, offsets=offsets)).lower(
        *[arg((c, 4 * lines)) for c in columns]).compile()
    unpack = jax.jit(lambda p: pallas_lines.unpack_lines(
        p, columns=columns, offsets=offsets)).lower(
        arg((lines, 128))).compile()
    for compiled in (pack, unpack):
        assert compiled.as_text().count("tpu_custom_call") == 1
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def _table_gathers(text, table):
    """(rows gathered, the op's stage path) of every gather in the optimised
    HLO whose OPERAND has the shape `table` (a regex)."""
    found = []
    for comp in text.split("\n\n"):
        shapes = dict(re.findall(r"%(\S+) = (\S+?)[{ ]", comp))
        for out, operand, line in re.findall(
                r"= \w+\[(\d+)[^\n]*? gather\(%(\S+?),([^\n]*)", comp):
            if re.fullmatch(table, shapes.get(operand, "").split("{")[0]):
                found.append((int(out), re.search(r'op_name="([^"]*)"',
                                                  line).group(1)))
    return found


def _deepfm_scan(one_chip, rows, dim, K=2, B=4096):
    """A DeepFM cell's K-step scan at its real sizes, compiled."""
    from openembedding_tpu.model import Trainer
    from openembedding_tpu.models import make_deepfm
    tr = Trainer(make_deepfm(vocabulary=rows, dim=dim, hidden=(400, 400, 400),
                             compute_dtype=jnp.bfloat16),
                 embed.Adagrad(learning_rate=0.05))
    sample = {"sparse": {"categorical": np.zeros((B, 26), np.int32)},
              "dense": np.zeros((B, 13), np.float32),
              "label": np.zeros((B,), np.float32)}
    with jax.enable_x64(False):  # the cells' own setting
        state = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(tr.init, sample))
        stacked = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct((K,) + x.shape, x.dtype,
                                           sharding=one_chip), sample)
        return tr.jit_train_many().lower(state, stacked).compile()


def _deepfm_scan_text(one_chip, rows, dim, K=2, B=4096):
    """The optimised HLO of that scan."""
    return _deepfm_scan(one_chip, rows, dim, K, B).as_text()


def test_shared_plan_scan_gathers_the_table_once_a_step_and_copies_none(one_chip):
    """`deepfm9.train_zipf`'s scan at its real sizes (2 steps): the 2^25 x
    (10 + 10) table is held as 2^23 lines of four rows (`ops/sparse.py` "FOUR
    ROWS A LANE LINE"; 4 GiB, and the program fits the chip's 15.75 GiB with
    both forms alive at its two ends). Two conditionals over the four rungs
    (the pull's gather, the apply's row math and merge); the table is
    gathered from in the pull's four branches alone (a step runs one): LINES
    `f32[W,128]`, W slots each, n only on the full-size rung, nowhere once a
    position and nowhere 20 columns wide; it is written by ONE
    `scatter_rows_dma` custom call under `sparse.apply` after the apply's
    switch, by no scatter of XLA's, and copied nowhere at all: the pack and
    the unpack at the scan's two ends are one custom call each
    (`ops/pallas_lines.py`), the program's only other two."""
    rows = 1 << 25
    compiled = _deepfm_scan(one_chip, rows, 9)
    text = compiled.as_text()
    table = rf"f32\[{rows // 4},128\]"
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 15.75 * 2**30
    assert text.count(" conditional(") == 2
    calls = re.findall(r'custom_call_target="tpu_custom_call"[^\n]*?'
                       r'op_name="([^"]*)"', text)
    assert sorted(c.split("/")[-2] for c in calls) == [
        "pack_lines", "scatter_rows_dma", "unpack_lines"], calls
    kernel, = [c for c in calls if "scatter_rows_dma" in c]
    assert "while/body" in kernel and "sparse.apply/" in kernel
    assert not any("while/body" in c for c in calls if c != kernel), calls
    assert not re.findall(rf"= {table}\S* fusion\([^\n]*/scatter", text)
    assert not re.findall(rf"f32\[{rows},(?:20|32)\]", text)  # no row form
    assert not re.findall(rf"= {table}\S* (?:copy|copy-start)\(", text)
    assert not re.findall(r"= f32\[\d+,20\]\S* gather\(", text)
    gathers = sorted(_table_gathers(text, table))
    assert [g[0] for g in gathers] == list(apply_ladder(N)), gathers
    assert all("sparse.pull/" in path and "sparse.apply" not in path
               and ("sparse.full_size" in path) == (n == N)
               for n, path in gathers), gathers


def test_dim64_scan_writes_its_lane_aligned_table_by_row_dmas_in_place(one_chip):
    """`deepfm64.train_zipf`'s scan at its real sizes (2 steps): the 2^22 x
    128 packed table is written by `ops/pallas_scatter.py`'s kernel, ONE
    custom call under `sparse.apply` after the apply's switch and no scatter
    of XLA's into it, with NO copy of the 2 GiB table inside a step (the
    custom call is in place as the scatter was); the 2^22 x 2 first-order
    table keeps XLA's scatter in fast memory."""
    rows = 1 << 22
    text = _deepfm_scan_text(one_chip, rows, 64)
    table, first_order = rf"f32\[{rows},128\]", rf"f32\[{rows},2\]"
    kernels = _row_dma_calls(text, table)
    assert len(kernels) == 1 == text.count("tpu_custom_call"), kernels
    assert "sparse.apply/" in kernels[0], kernels
    assert not re.findall(rf"= {table}\S* fusion\([^\n]*/scatter", text)
    # one relayout of the table a DISPATCH at the scan's exit, as the parent's
    # (the unpack; ROADMAP Speed 8), and none inside a step
    copies = re.findall(rf"= {table}\S* (?:copy|copy-start)\([^\n]*?"
                        r'op_name="([^"]*)"', text)
    assert copies == ["jit(train_many)/while"], copies
    scatters = re.findall(rf"= {first_order}(\S*) fusion\([^\n]*/scatter", text)
    assert len(scatters) == 1 and "S(1)" in scatters[0], scatters


S4, PER_CHIP = 4, 4096     # `deepfm9x4.train_zipf`: four shards, N positions each


def _four_chip_scan(topo, K=2, vocabulary=1 << 27, **kw):
    """`deepfm9x4.train_zipf`'s program for the 2x2 described chips: 2^27
    rows in four shards, 4096 examples a chip, exact mode, bf16 wire ->
    (the jitted K-step scan, its state's and its batches' shapes). Tracing
    only: the state's shapes come from the CPU mesh (nothing is made: 10.7 GB
    of table). `kw`: `MeshTrainer`'s and `make_deepfm`'s, by name."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    B = S4 * PER_CHIP
    model_kw = {k: kw.pop(k) for k in ("hashed", "capacity") if k in kw}

    def trainer(devices):
        return MeshTrainer(
            make_deepfm(vocabulary=vocabulary, dim=9, hidden=(400, 400, 400),
                        compute_dtype=jnp.bfloat16, **model_kw),
            embed.Adagrad(learning_rate=0.05), mesh=make_mesh(devices),
            wire="bf16", **kw)
    one = {"sparse": {"categorical": np.zeros((B, 26), np.int32)},
           "dense": np.zeros((B, 13), np.float32),
           "label": np.zeros((B,), np.float32)}
    shapes = jax.eval_shape(trainer(jax.devices()[:S4]).init, one)
    tr = trainer(topo.devices)
    state = jax.tree_util.tree_map(
        lambda s, p: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(tr.mesh, p)),
        shapes, tr._state_pspec_tree(shapes))
    feed = NamedSharding(tr.mesh, P(None, tr.axis))
    stacked = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((K,) + x.shape, x.dtype,
                                       sharding=feed), one)
    many = tr.jit_train_many(jax.tree_util.tree_map(
        lambda x: np.zeros((K,) + x.shape, x.dtype), one), state)
    return many, state, stacked


def test_four_chip_scan_has_no_per_slot_op_over_s_x_capacity_rows(topo):
    """`deepfm9x4.train_zipf`'s program as traced for the 2x2 described
    chips: every bucket array of the wire has S x cap = 4 x 106,496 rows.
    PR 30's scan scattered the ids and the gradient payload into them and
    gathered the pulled rows out of them slot by slot (3.3 ms of a 28.3 ms
    step on the chip, PERF.md); the only scatters and gathers of that length
    left are the owner's, in a step whose received ids do not fit its working
    size."""
    many, state, stacked = _four_chip_scan(topo)
    rows = S4 * N
    sites = guards.primitive_sites(
        many, ("scatter", "scatter-add", "gather"), state, stacked)
    long = [(name, stack) for name, stack, shp in sites
            if any(s and s[0] == rows for s in shp)]
    assert long and all("exchange.full_size" in stack for _, stack in long), \
        [site for site in long if "exchange.full_size" not in site[1]]
    a2a = [c for c in guards.collective_sequence(many, state, stacked)
           if c[0] == "all_to_all"]
    assert len(a2a) == 3  # ids, rows, grads: one dim-group, as before


def _shard_gathers(many, state, stacked):
    """(rows gathered, name stack) of every gather the program traces whose
    operand is two-dimensional and at least 2^20 rows long: a shard."""
    return sorted((shp[-1][0], stack) for _, stack, shp in
                  guards.primitive_sites(many, ("gather",), state, stacked)
                  if len(shp[0]) == 2 and shp[0][0] >= 1 << 20)


def test_four_chip_scan_gathers_the_shard_at_the_owners_serve_alone(
        topo, monkeypatch):
    """The owner plans once a step (`parallel/sharded.py`): in the cell's
    scan the only gathers from the packed shard (2^25 x 20, held as 2^23
    lines of four rows: `ops/sparse.py` "FOUR ROWS A LANE LINE") outside
    `exchange.full_size` are the serve's, one a rung of `apply_ladder(n)`
    under `exchange.owner_serve/.../sparse.pull` (a step runs one), and none
    once a received slot; the compact apply gathers nothing from the shard.
    The full-size branches keep the parent's program: the serve once a slot
    of S x cap, the apply over its own ladder. Three all-to-alls still, and
    `exchange.owner_plans{path="shared"}` once for the table."""
    from openembedding_tpu.utils import metrics
    monkeypatch.setattr(metrics, "_REGISTRY", {})
    many, state, stacked = _four_chip_scan(topo)
    found = _shard_gathers(many, state, stacked)
    rep = metrics.report()
    assert rep['exchange.owner_plans{path="shared"}'] == 1
    assert 'exchange.owner_plans{path="per_slot"}' not in rep
    fits = [(n, stack) for n, stack in found
            if "exchange.full_size" not in stack]
    assert [n for n, _ in fits] == list(apply_ladder(N)), fits
    assert all("exchange.owner_serve" in stack and "sparse.pull" in stack
               and "exchange.owner_apply" not in stack
               and ("sparse.full_size" in stack) == (n == N)
               for n, stack in fits), fits
    full = [(n, stack) for n, stack in found if "exchange.full_size" in stack]
    assert sorted(n for n, stack in full if "owner_serve" in stack) == [S4 * N]
    assert sorted(n for n, stack in full if "owner_apply" in stack) == \
        list(apply_ladder(S4 * N))
    assert len(full) == 1 + len(apply_ladder(S4 * N))
    a2a = [c for c in guards.collective_sequence(many, state, stacked)
           if c[0] == "all_to_all"]
    assert len(a2a) == 3
    # the shard is in the line form (2^23 lines of four rows): every gather
    # above reads LINES, and what writes it is the row-DMA kernel, one
    # instance in the compact apply and one in the full-size apply, each
    # after its switch (beside it, for the lowerings that are no TPU's, XLA's
    # scatter: `jax.lax.platform_dependent` traces both)
    shard = (1 << 23, 128)
    assert all(shp[0] == shard for _, _, shp in guards.primitive_sites(
        many, ("gather",), state, stacked)
        if len(shp[0]) == 2 and shp[0][0] >= 1 << 20)
    kernels = [stack for _, stack, shp in guards.primitive_sites(
        many, ("pallas_call",), state, stacked)
        if shard in shp and "sparse.apply" in stack]  # not pack / unpack
    assert len(kernels) == 2, kernels
    assert sorted("exchange.full_size" in k for k in kernels) == [False, True]
    assert all("exchange.owner_apply" in k and "sparse.apply" in k
               for k in kernels), kernels


@pytest.mark.parametrize("kw", [
    dict(pipeline_steps=True),
    dict(vocabulary=-1, hashed=True, capacity=1 << 27)],
    ids=["pipelined", "hash_table"])
def test_four_chip_scan_with_no_apply_to_share_with_serves_per_slot(
        topo, kw, monkeypatch):
    """What cannot share a plan keeps the parent's program: a pipelined scan
    serves step t + 1 before step t's apply writes the shard, a hash table
    probes per slot. `exchange.owner_plans{path="per_slot"}`, no plan made
    (`plan_packed_rows` is not traced on the mesh), and the shard gathered
    from once a received slot (W = n) at the serve and over the apply's
    ladder at the apply, never at one of its rungs at the serve."""
    from openembedding_tpu.parallel import sharded
    from openembedding_tpu.utils import metrics
    monkeypatch.setattr(metrics, "_REGISTRY", {})
    monkeypatch.setattr(sharded, "plan_packed_rows", None)   # a call raises
    many, state, stacked = _four_chip_scan(topo, **kw)
    found = _shard_gathers(many, state, stacked)
    rep = metrics.report()
    assert 'exchange.owner_plans{path="shared"}' not in rep
    assert rep['exchange.owner_plans{path="per_slot"}'] >= 1
    fits = [(n, stack) for n, stack in found
            if "exchange.full_size" not in stack]
    # (the pipelined scan traces prologue, body and epilogue, and its
    # conflict patch reads the S x cap received slots again)
    serve = {n for n, stack in fits if "owner_serve" in stack}
    assert N in serve and serve <= {N, S4 * N}, fits
    assert {n for n, stack in fits if "owner_apply" in stack} == \
        set(apply_ladder(N)), fits


# the language-model cells' cores: (B, S, Hq, Hkv, D, Dv); 8,192 at 128 / 128
# is the longest sequence the kernel's tiling takes
CORES = {"joyai_keys192_values128": (2, 4096, 32, 32, 192, 128),
         "nemotron_32_heads_over_2": (2, 4096, 32, 2, 128, 128),
         "solar_8k_8_heads_over_1": (1, 8192, 8, 1, 128, 128)}
SCORE_BLOCK = 2 * 32 * 512 * 512   # elements of one block's scores


def _core_text(one_chip, shape, dtype=jnp.bfloat16):
    """Optimised HLO and temporaries of value_and_grad through the entry,
    under the stage name the models give it."""
    from openembedding_tpu.models import nemotron_h as nh
    from openembedding_tpu.utils import trace
    B, S, Hq, Hkv, D, Dv = shape

    def loss(q, k, v):
        with trace.scope("attn", "core"):
            o = nh.blockwise_causal_attention(q, k, v, block=512)
        return jnp.sum(o.astype(jnp.float32))

    def arg(*dims):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        arg(B, S, Hq, D), arg(B, S, Hkv, D), arg(B, S, Hkv, Dv)).compile()
    return compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes


def _score_blocks(text):
    """Element counts of the f32 arrays the text names that are at least a
    block of queries by a block of keys in their two minor dimensions."""
    shapes = ([int(d) for d in dims.split(",")]
              for dims in set(re.findall(r"f32\[([0-9,]+)\]", text)))
    return [int(np.prod(shape)) for shape in shapes
            if len(shape) >= 2 and min(shape[-2:]) >= 512]


@pytest.mark.parametrize("shape", CORES.values(), ids=CORES.keys())
def test_attention_core_lowers_to_the_fused_kernel_on_the_tpu(one_chip, shape):
    text, temp = _core_text(one_chip, shape)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2                      # forward, backward
    assert all("attn.core" in line for line in calls), calls
    assert max(_score_blocks(text), default=0) < SCORE_BLOCK
    assert temp < 1 << 30


def test_attention_core_the_tiling_refuses_lowers_to_the_plain_body(one_chip):
    """A sequence of 4,000 splits into no block of 128 rows: the plain
    blockwise body, its f32 scores an array of the program."""
    text, _ = _core_text(one_chip, (2, 4000, 32, 2, 128, 128))
    assert "tpu_custom_call" not in text
    assert max(_score_blocks(text)) >= SCORE_BLOCK


def test_solar_open2_scan_fits_the_chip_at_ten_experts(one_chip):
    """`benchmark`'s `solar-open2.train_8k` at its real sizes: the 4-step scan
    compiles for the described v5e (the compiler raises where it does not
    fit), with one fused attention core forward and backward, three chunked
    delta-rule scans (their solves custom calls) and no second copy of the
    experts' state with the expert axis second-minor."""
    import json

    from openembedding_tpu import models
    from openembedding_tpu.model import Trainer
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                        "solar-open2-250b-l4-h8of64.json")
    with open(path) as f:
        cfg = json.load(f)

    def at(path):
        node = cfg
        for key in path.split("."):
            node = node[key]
        return node
    model = models.make_solar_open2(
        compute_dtype=jnp.dtype(cfg["tower_dtype"]),
        **{kw: at(path) for path, kw in cfg["make_keywords"].items()})
    tr = Trainer(model, embed.Adagrad(learning_rate=cfg["learning_rate"],
                                      initial_accumulator_value=0.1,
                                      epsilon=1e-7))
    K, B, S = 4, 1, 8192
    sample = {"sparse": {"token": np.zeros((B, S), np.int32)},
              "label": np.zeros((B, S), np.int32)}
    with jax.enable_x64(False):  # the cell's own setting; the suite's is on
        state = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(tr.init, sample))
        ids = jax.ShapeDtypeStruct((K, B, S), jnp.int32, sharding=one_chip)
        text = tr.jit_train_many().lower(
            state, {"sparse": {"token": ids}, "label": ids}).compile().as_text()
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        (state.dense_params, state.tables["token"].weights))) == 966_701_720
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    assert not re.search(r"f32\[10,(4096,1280|1280,4096)\]\{2,0,1", text)


def test_ouro_walk_is_one_scanned_body_that_stacks_no_logits(one_chip):
    """`benchmark`'s `ouro.train_4k` at its real widths, 2 of its 8 layers
    (22 s of compile): the 4-step scan holds three loops (the steps, the walk
    over the four passes, its transpose: an unrolled walk would hold one),
    the fused core inside the pass, what a pass leaves for the backward pass
    stacked over the passes as (4, 1, 4096, ...) arrays of the state's and
    the core's shapes, and NO `f32[.., 4096, 49152]` stacked over them: one
    exit's logits are alive at a time."""
    import json

    from openembedding_tpu import models
    from openembedding_tpu.model import Trainer
    path = os.path.join(os.path.dirname(__file__), "..", "benchmark", "configs",
                        "ouro-2.6b-ut4.json")
    with open(path) as f:
        cfg = dict(json.load(f), num_hidden_layers=2)
    model = models.make_ouro(
        compute_dtype=jnp.dtype(cfg["tower_dtype"]),
        **{kw: cfg[key] for key, kw in cfg["make_keywords"].items()})
    tr = Trainer(model, embed.Adagrad(learning_rate=cfg["learning_rate"],
                                      initial_accumulator_value=0.1,
                                      epsilon=1e-7))
    K, B, S, T = 4, 1, 4096, cfg["total_ut_steps"]
    sample = {"sparse": {"token": np.zeros((B, S), np.int32)},
              "label": np.zeros((B, S), np.int32)}
    with jax.enable_x64(False):  # the cell's own setting; the suite's is on
        state = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            jax.eval_shape(tr.init, sample))
        ids = jax.ShapeDtypeStruct((K, B, S), jnp.int32, sharding=one_chip)
        compiled = tr.jit_train_many().lower(
            state, {"sparse": {"token": ids}, "label": ids}).compile()
    text = compiled.as_text()
    assert len(re.findall(r"\bwhile\(", text)) == 3
    assert "flash_attention_fwd" in text and "flash_attention_bwd" in text
    stacked = set(re.findall(r"[a-z0-9]+\[%d,[0-9,]+\]" % T, text))
    assert f"bf16[{T},1,4096,2048]" in stacked, stacked
    assert not [s for s in stacked if "49152" in s], stacked
    assert "f32[1,4096,49152]" in text or "f32[4096,49152]" in text
    # 2 layers' state 2.27 GiB + temporaries 4.96 here; two exits' logits
    # and their gradients more would be 3 GiB over
    assert compiled.memory_analysis().temp_size_in_bytes < 5.5 * 2 ** 30
