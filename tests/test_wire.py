"""Fused multi-table exchange + quantized wire payloads (`ops/wire.py`,
`parallel/sharded.grouped_*`).

Covers the round-6 tentpole contracts:
- 3 all_to_alls per DIM-GROUP (not per table), pinned at the HLO level for a
  3-table / 2-group model (6, against 9 with one group per table);
- the fused exchange with fp32 wire is BIT-identical to the same exchange
  run one table a group (grouping only shares the wire, never the math);
- bf16 (default) / int8 (opt-in) wire: pull rows and pushed grads round-trip
  within format tolerance, duplicate-count lanes and overflow counters stay
  EXACT, table storage stays full-precision fp32;
- the static wire-cost model: bf16 moves >= 1.8x fewer exchange bytes/step
  than fp32.

The suite-wide default wire is pinned to fp32 in tests/conftest.py (parity
tests elsewhere assert exact agreement); every lossy-format test here passes
`wire=` explicitly.
"""

import numpy as np
import pytest

import flax.linen as nn
import jax
import jax.numpy as jnp

import openembedding_tpu as embed
from openembedding_tpu.model import EmbeddingModel
from openembedding_tpu.ops import wire
from openembedding_tpu.parallel import MeshTrainer, make_mesh

S = 8
B = 4 * S
FMTS = ("fp32", "bf16", "int8")


# ---------------------------------------------------------------------------
# wire codec units
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fmt", FMTS)
def test_counts_roundtrip_exact(fmt):
    """Duplicate counts must survive the wire bit-exactly in EVERY format —
    they divide/weight optimizer updates (1 fp32 / 2 bf16 / 4 int8 lanes)."""
    counts = jnp.asarray(
        np.array([0, 1, 2, 3, 127, 128, 255, 65536, (1 << 30) + 17, 4096],
                 np.int32))
    lanes = wire.counts_to_lanes(counts, fmt)
    assert lanes.shape == (10, wire.count_lanes(fmt))
    # lanes travel in the CARRIER dtype (bf16 ships as uint16 so XLA:CPU's
    # bf16->f32 float normalization can't widen the compiled collective)
    assert lanes.dtype == wire.wire_carrier_dtype(fmt)
    np.testing.assert_array_equal(np.asarray(wire.lanes_to_counts(lanes)),
                                  np.asarray(counts))


@pytest.mark.parametrize("fmt", FMTS)
def test_rows_roundtrip_within_format_tolerance(fmt):
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((64, 16)).astype(np.float32) * 3.0
    rows[5] = 0.0  # all-zero row: must decode to exact zeros (int8 scale 0)
    enc = wire.encode_rows(jnp.asarray(rows), fmt)
    assert enc.shape[1] == wire.rows_wire_width(16, fmt)
    dec = np.asarray(wire.decode_rows(enc, 16, fmt))
    if fmt == "fp32":
        np.testing.assert_array_equal(dec, rows)
    elif fmt == "bf16":
        np.testing.assert_allclose(dec, rows, rtol=2 ** -8, atol=1e-7)
    else:  # int8: per-row max-abs scaling -> error <= scale/2 per element
        step = np.abs(rows).max(axis=1, keepdims=True) / 127.0
        assert np.all(np.abs(dec - rows) <= step * 0.5 + 1e-7)
    np.testing.assert_array_equal(dec[5], 0.0)


@pytest.mark.parametrize("fmt", FMTS)
def test_grads_payload_and_empty_slots(fmt):
    """encode_grads folds grads + exact counts into one payload row; a ZERO
    payload row (what empty bucket slots carry) decodes to grad 0, count 0."""
    rng = np.random.default_rng(1)
    g = rng.standard_normal((32, 8)).astype(np.float32)
    counts = jnp.asarray(rng.integers(0, 1 << 20, 32).astype(np.int32))
    enc = wire.encode_grads(jnp.asarray(g), counts, fmt)
    assert enc.shape[1] == wire.grads_wire_width(8, fmt)
    dec_g, dec_c = wire.decode_grads(enc, 8, fmt)
    np.testing.assert_array_equal(np.asarray(dec_c), np.asarray(counts))
    tol = {"fp32": 0.0, "bf16": 2 ** -8, "int8": 1 / 64}[fmt]
    np.testing.assert_allclose(np.asarray(dec_g), g, rtol=tol,
                               atol=tol * np.abs(g).max() + 1e-7)
    zero_g, zero_c = wire.decode_grads(jnp.zeros_like(enc), 8, fmt)
    np.testing.assert_array_equal(np.asarray(zero_g), 0.0)
    np.testing.assert_array_equal(np.asarray(zero_c), 0)


def test_concat_split_buckets_mixed_int_widths():
    """int32 + int64 bucket arrays fuse onto an int64 wire and narrow back;
    sentinels (-1) survive both directions."""
    from openembedding_tpu.ops.dedup import (concat_owner_buckets,
                                             split_owner_buckets)
    a = jnp.asarray(np.array([[1, -1, 5], [7, 3, -1]], np.int32))
    b = jnp.asarray(np.array([[1 << 40, -1], [-1, (1 << 33) + 9]], np.int64))
    fused = concat_owner_buckets([a, b])
    assert fused.dtype == jnp.int64 and fused.shape == (2, 5)
    back = split_owner_buckets(fused, [(3, False, a.dtype),
                                       (2, False, b.dtype)])
    np.testing.assert_array_equal(np.asarray(back[0]), np.asarray(a))
    assert back[0].dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(back[1]), np.asarray(b))


def test_concat_split_buckets_pair_widening():
    """A split-pair table beside a single-lane array table widens the group
    onto the pair wire; the array table's segment narrows back with its
    sentinels intact (`ops/id64` machinery)."""
    from openembedding_tpu.ops.dedup import (concat_owner_buckets,
                                             split_owner_buckets)
    from openembedding_tpu.ops.id64 import np_split_ids
    ids64 = np.array([[(1 << 45) + 3, -1], [-1, (1 << 62) - 5]], np.int64)
    pair = jnp.asarray(np_split_ids(ids64))                  # (2, 2, 2)
    flat = jnp.asarray(np.array([[4, -1, 0], [-1, 2, 7]], np.int32))
    fused = concat_owner_buckets([pair, flat])
    assert fused.ndim == 3 and fused.shape == (2, 5, 2)
    back = split_owner_buckets(fused, [(2, True, pair.dtype),
                                       (3, False, flat.dtype)])
    np.testing.assert_array_equal(np.asarray(back[0]), np.asarray(pair))
    np.testing.assert_array_equal(np.asarray(back[1]), np.asarray(flat))


# ---------------------------------------------------------------------------
# the 3-table / 2-dim-group model the fused-exchange pins train
# ---------------------------------------------------------------------------


class _ThreeTower(nn.Module):
    """Reads two dim-8 tables + one dim-1 table -> logits (B,)."""

    @nn.compact
    def __call__(self, embedded, dense):
        bias = self.param("bias", nn.initializers.zeros, (1,), jnp.float32)
        out = (jnp.sum(embedded["a"].astype(jnp.float32), axis=(1, 2))
               + jnp.sum(embedded["b"].astype(jnp.float32), axis=(1, 2))
               + jnp.sum(embedded["w"][..., 0].astype(jnp.float32), axis=1))
        return out + bias[0]


def _three_table_model(vocab=64):
    """3 PS tables in 2 dim-groups: dim-8 {a (array), b (hash)} + dim-1 {w}.
    The hash table keys in int64 under the suite's x64 config, so the fused
    id wire exercises the mixed int32/int64 promotion path too."""
    embs = [
        embed.Embedding(vocab, 8, name="a",
                        embeddings_initializer=embed.Constant(0.05)),
        embed.Embedding(-1, 8, name="b", capacity=4096,
                        embeddings_initializer=embed.Constant(0.02)),
        embed.Embedding(vocab, 1, name="w",
                        embeddings_initializer=embed.Constant(0.0)),
    ]
    return EmbeddingModel(_ThreeTower(), embs)


def _batch(rng, vocab=64, dupes=True, hash_space=1 << 40,
           hash_dtype=np.int64):
    a = rng.integers(0, vocab, (B, 4)).astype(np.int32)
    b = rng.integers(0, hash_space, (B, 3)).astype(hash_dtype)
    if dupes:  # duplicate-heavy streams: the count lanes must carry > 1
        a[:, 0] = 7
        b[:, 0] = hash_space - 13
    w = rng.integers(0, vocab, (B, 4)).astype(np.int32)
    return {"sparse": {"a": a, "b": b, "w": w},
            "label": rng.integers(0, 2, (B,)).astype(np.float32)}


class _OneGroupPerTable(MeshTrainer):
    """The reference the grouping pins compare against: the same exchange
    with every table alone on its wire."""

    def _exchange_groups(self, ps_specs):
        return [[n] for g in super()._exchange_groups(ps_specs) for n in g]


def _train(trainer, batches, state=None):
    if state is None:
        state = trainer.init(batches[0])
    if isinstance(trainer, MeshTrainer):
        step = trainer.jit_train_step(batches[0], state)
    else:
        step = trainer.jit_train_step()
    losses = []
    for b in batches:
        state, m = step(state, b)
        losses.append(float(m["loss"]))
    return state, losses


def _probe_tables(trainer, state, batches, vocab=64):
    """Deterministic table reads for comparison across trainers: the array
    tables read fully, the hash table reads every id the batches trained."""
    from openembedding_tpu.embedding import lookup as single_lookup
    from openembedding_tpu.parallel.sharded import sharded_lookup
    from jax.sharding import PartitionSpec as P
    from functools import partial
    out = {}
    probes = {"a": np.arange(vocab, dtype=np.int32),
              "b": np.unique(np.concatenate(
                  [b["sparse"]["b"].reshape(-1) for b in batches])),
              "w": np.arange(vocab, dtype=np.int32)}
    for name, probe in probes.items():
        spec = trainer.model.specs[name]
        if isinstance(trainer, MeshTrainer):
            pull = jax.jit(jax.shard_map(
                partial(sharded_lookup, spec, axis=trainer.axis),
                mesh=trainer.mesh,
                in_specs=(trainer._table_pspec(spec), P()),
                out_specs=P(), check_vma=False))
            out[name] = np.asarray(pull(state.tables[name],
                                        jnp.asarray(probe)))
        else:
            out[name] = np.asarray(single_lookup(
                spec, state.tables[name], jnp.asarray(probe)))
    return out


# ---------------------------------------------------------------------------
# fused-exchange pins
# ---------------------------------------------------------------------------


def test_fused_step_compiles_three_all_to_alls_per_dim_group():
    """THE acceptance pin: a 3-table model in 2 dim-groups compiles to 6
    all_to_alls per train step (3 per GROUP); one group per table compiles
    the same model to 9."""
    import re

    def count_a2a(trainer_cls):
        rng = np.random.default_rng(0)
        tr = trainer_cls(_three_table_model(),
                         embed.Adagrad(learning_rate=0.05), mesh=make_mesh())
        b = _batch(rng)
        state = tr.init(b)
        step = tr.jit_train_step(b, state)
        txt = step.lower(state, b).compile().as_text()
        return len(re.findall(r" all-to-all(?:-start)?\(", txt))

    assert count_a2a(MeshTrainer) == 6, "expected 3 a2a per dim-group"
    assert count_a2a(_OneGroupPerTable) == 9, \
        "one group per table: expected 3 a2a per table"


def test_fused_fp32_bitexact_vs_per_table_protocol():
    """Grouping shares the WIRE, never the math: with fp32 wire the fused
    exchange must reproduce one group per table bit for bit (same dedup,
    same bucket contents, same apply order)."""
    rng = np.random.default_rng(1)
    batches = [_batch(rng) for _ in range(3)]

    def run(trainer_cls):
        tr = trainer_cls(_three_table_model(),
                         embed.Adagrad(learning_rate=0.1), mesh=make_mesh(),
                         wire="fp32")
        state, losses = _train(tr, batches)
        return _probe_tables(tr, state, batches), losses

    fused, l_fused = run(MeshTrainer)
    per_table, l_per = run(_OneGroupPerTable)
    np.testing.assert_array_equal(l_fused, l_per)
    for name in fused:
        np.testing.assert_array_equal(fused[name], per_table[name])


@pytest.mark.parametrize("fmt", ["bf16", "int8"])
def test_quantized_wire_parity_and_fp32_storage(fmt):
    """Lossy wire formats: trained tables stay within format tolerance of the
    fp32-wire run (pull rows AND pushed grads both cross the wire every
    step), storage dtype stays fp32, and the duplicate-heavy stream keeps
    count-dependent updates sane (mangled count lanes would be gross)."""
    rng = np.random.default_rng(2)
    batches = [_batch(rng) for _ in range(3)]

    def run(wire_fmt):
        tr = MeshTrainer(_three_table_model(),
                         embed.Adagrad(learning_rate=0.1), mesh=make_mesh(),
                         wire=wire_fmt)
        state, losses = _train(tr, batches)
        for ts in state.tables.values():
            assert ts.weights.dtype == jnp.float32  # storage never quantizes
        return _probe_tables(tr, state, batches), losses

    exact, l_exact = run("fp32")
    lossy, l_lossy = run(fmt)
    # pull rows + grads each round once per step; 3 steps of Adagrad compound
    tol = 0.02 if fmt == "bf16" else 0.06
    for name in exact:
        np.testing.assert_allclose(lossy[name], exact[name], rtol=tol,
                                   atol=tol)
    np.testing.assert_allclose(l_lossy, l_exact, rtol=tol)
    assert max(abs(np.asarray(v)).max() for v in lossy.values()) > 0


@pytest.mark.parametrize("fmt", ["bf16", "int8"])
def test_overflow_drop_paths_unchanged_by_wire(fmt):
    """Bounded buckets under capacity pressure: overflow counters are an
    ID-side property and must be IDENTICAL across wire formats; dropped ids
    still pull zeros / drop grads (training stays finite)."""
    rng = np.random.default_rng(3)
    batches = [_batch(rng) for _ in range(2)]

    def run(wire_fmt):
        tr = MeshTrainer(_three_table_model(),
                         embed.Adagrad(learning_rate=0.1), mesh=make_mesh(),
                         capacity_factor=0.25, wire=wire_fmt)
        state = tr.init(batches[0])
        step = tr.jit_train_step(batches[0], state)
        oflow = {}
        for b in batches:
            state, m = step(state, b)
            for k, v in m["stats"].items():
                if k.endswith("_overflow"):
                    oflow[k] = oflow.get(k, 0) + int(np.asarray(v))
            assert np.isfinite(float(m["loss"]))
        return oflow

    o_exact = run("fp32")
    o_lossy = run(fmt)
    # the duplicate-saturated streams overflow the 0.25-factor buckets
    assert sum(o_exact.values()) > 0
    assert o_lossy == o_exact


def test_wire_cost_model_and_gauges():
    """Static cost model: bf16 >= 1.8x fewer exchange bytes/step than fp32
    int8 beats bf16, 3 collectives per dim-group; the trainer publishes the
    gauges at trace time."""
    from openembedding_tpu.utils import metrics as M

    tables = [{"dim": 16, "cap": 128, "pair": False, "id_itemsize": 4},
              {"dim": 16, "cap": 128, "pair": False, "id_itemsize": 8},
              {"dim": 1, "cap": 64, "pair": False, "id_itemsize": 4}]
    fp32 = wire.exchange_cost(tables, S, "fp32")
    bf16 = wire.exchange_cost(tables, S, "bf16")
    int8 = wire.exchange_cost(tables, S, "int8")
    assert fp32["collectives_per_step"] == 6  # 2 dim-groups
    one = wire.exchange_cost(tables, 1, "fp32")  # one shard: nothing ships
    assert one["collectives_per_step"] == 0 and one["bytes_per_step"] == 0
    assert fp32["bytes_per_step"] / bf16["bytes_per_step"] >= 1.8
    assert int8["bytes_per_step"] < bf16["bytes_per_step"]

    rng = np.random.default_rng(4)
    tr = MeshTrainer(_three_table_model(), embed.Adagrad(learning_rate=0.1),
                     mesh=make_mesh(), wire="bf16")
    b = _batch(rng)
    state = tr.init(b)
    _train(tr, [b], state=state)
    assert tr.last_wire_cost is not None
    assert tr.last_wire_cost["collectives_per_step"] == 6
    vals = M.report()
    assert vals.get("exchange.collectives_per_step") == 6.0
    assert vals.get("exchange.wire_bytes_per_step", 0) > 0


def test_grouped_pair_wire_x64_off():
    """Under x64-off the hash table keys in the split-pair layout; grouped
    with an int32 array table the fused id wire widens to pairs. Parity vs
    one group per table stays exact (fp32 wire)."""
    with jax.enable_x64(False):
        rng = np.random.default_rng(5)
        # int32 ids (< 2^31: nothing to truncate); adapt_batch_ids widens
        # them onto the pair key layout at the protocol entry
        batches = [_batch(rng, hash_space=1 << 20, hash_dtype=np.int32)
                   for _ in range(2)]

        def run(trainer_cls):
            tr = trainer_cls(_three_table_model(),
                             embed.Adagrad(learning_rate=0.1),
                             mesh=make_mesh(), wire="fp32")
            state, losses = _train(tr, batches)
            assert state.tables["b"].keys.ndim == 2  # pair-keyed
            return losses

        np.testing.assert_array_equal(run(MeshTrainer),
                                      run(_OneGroupPerTable))


# ---------------------------------------------------------------------------
# round 17: per-table wire (dim-groups split on (dim, fmt))
# ---------------------------------------------------------------------------


def test_mixed_wire_splits_dim_groups_and_pins_a2a_count():
    """Per-table wire: `wire={table: fmt}` resolves once at trace time and
    the fused exchange keys its groups on (dim, fmt) — {a: int8, *: fp32}
    splits the dim-8 {a, b} group in two (3 groups -> 9 a2as) with both s8
    and f32 payload lanes in the compiled HLO, while a format-uniform dict
    is an identity split that compiles the round-13 program unchanged
    (6 a2as, same bytes as the plain-string config)."""
    import re

    def compile_txt(wire_cfg):
        rng = np.random.default_rng(6)
        tr = MeshTrainer(_three_table_model(),
                         embed.Adagrad(learning_rate=0.05), mesh=make_mesh(),
                         wire=wire_cfg)
        b = _batch(rng)
        state = tr.init(b)
        step = tr.jit_train_step(b, state)
        return step.lower(state, b).compile().as_text()

    def a2a_count(txt):
        return len(re.findall(r" all-to-all(?:-start)?\(", txt))

    def a2a_dtypes(txt):
        # result types on the definition head (tuple results list each
        # tensor), same parse the oelint hlo-budget pass pins bytes with
        out = set()
        for line in txt.splitlines():
            m = re.search(r" all-to-all(?:-start)?\(", line)
            if m:
                out |= {d for d in re.findall(
                    r"(pred|bf16|f32|s8|u8|s16|u16|s32|u32|s64|u64)\[",
                    line[:m.start()])}
        return out

    mixed = compile_txt({"a": "int8", "*": "fp32"})
    assert a2a_count(mixed) == 9, "mixed formats: expected 3 a2a groups"
    assert {"s8", "f32"} <= a2a_dtypes(mixed)
    uniform = compile_txt({"*": "fp32"})
    baseline = compile_txt("fp32")
    assert a2a_count(uniform) == 6
    assert a2a_count(baseline) == 6
    assert a2a_dtypes(uniform) == a2a_dtypes(baseline)


def test_mixed_wire_counts_lanes_bit_exact_and_gauges_truthful():
    """Mixed formats split a dim-group's payload wire but never the id side:
    under {a: int8, *: fp32} every count-lane-derived stat (dedup counts,
    bucket fill, shard loads, overflow) is BIT-identical to the all-fp32
    run, and the fp32-wired tables move only through the second-order logit
    shift a's quantized rows cause (~1e-8), orders of magnitude below a's
    own quantization error. The per-table `exchange.wire_dtype{table=}`
    gauges report the mixed wire truthfully."""
    from openembedding_tpu.utils import metrics as M

    rng = np.random.default_rng(7)
    batches = [_batch(rng) for _ in range(3)]

    def run(wire_cfg):
        M._REGISTRY.clear()
        tr = MeshTrainer(_three_table_model(),
                         embed.Adagrad(learning_rate=0.1), mesh=make_mesh(),
                         wire=wire_cfg)
        state = tr.init(batches[0])
        step = tr.jit_train_step(batches[0], state)
        stats = []
        for b in batches:
            state, m = step(state, b)
            stats.append({k: np.asarray(v) for k, v in m["stats"].items()})
        return _probe_tables(tr, state, batches), stats, M.report()

    exact, st_exact, _ = run("fp32")
    mixed, st_mixed, rep = run({"a": "int8", "*": "fp32"})
    # id/count lanes: every stat the exchange derives from ids is bitwise
    # unchanged by the payload-format split
    for se, sm in zip(st_exact, st_mixed):
        assert sorted(se) == sorted(sm)
        for k in se:
            np.testing.assert_array_equal(se[k], sm[k], err_msg=k)
    # a rides int8 (s8 lanes pinned in the HLO test above) within format
    # tolerance; the fp32-wired tables see no quantizer at all — their
    # drift is only the second-order logit shift from a's quantized rows
    np.testing.assert_allclose(mixed["a"], exact["a"], rtol=0.06, atol=0.06)
    d_rest = max(np.abs(mixed["b"] - exact["b"]).max(),
                 np.abs(mixed["w"] - exact["w"]).max())
    assert d_rest < 1e-6, d_rest
    assert rep['exchange.wire_dtype{table="a"}'] == 1.0   # s8 itemsize
    assert rep['exchange.wire_dtype{table="b"}'] == 4.0   # f32 itemsize
    assert rep['exchange.wire_dtype{table="w"}'] == 4.0


def test_policy_wire_cuts_bytes_and_never_costs_vs_global_int8():
    """`PlacementPolicy.recommend_wire` on skewed wide tables beside a dim-1
    table: int8 for the wide ones, fp32 for the narrow one (int8 WIDENS a
    dim-1 row: 1 B + scale lanes), so the mix ships no more exchange bytes
    than int8 everywhere and cuts the fp32 wire by the codec's ratio (ids
    and count lanes stay exact). Priced by the static model, which the
    hlo-budget's `wire_model_delta` 0 holds equal to the compiled a2as."""
    from openembedding_tpu.placement.policy import (PlacementPolicy,
                                                    TableTelemetry)
    skew = [(64, 0.3), (256, 0.45), (1024, 0.7), (4096, 0.9)]
    rec = PlacementPolicy(hot_budget_bytes=0).recommend_wire(
        [TableTelemetry("latent", 64, skew, 1e6),
         TableTelemetry("hashed", 64, skew, 1e6),
         TableTelemetry("first_order", 1, skew, 1e6)])
    assert rec == {"latent": "int8", "hashed": "int8", "first_order": "fp32"}

    def cost(fmts):
        return wire.exchange_cost(
            [{"dim": 64, "cap": 128, "pair": False, "id_itemsize": 4,
              "fmt": fmts["latent"]},
             {"dim": 64, "cap": 64, "pair": True, "id_itemsize": 8,
              "fmt": fmts["hashed"]},
             {"dim": 1, "cap": 128, "pair": False, "id_itemsize": 4,
              "fmt": fmts["first_order"]}], S, "fp32")["bytes_per_step"]

    mixed = cost(rec)
    assert mixed <= cost(dict.fromkeys(rec, "int8"))
    assert cost(dict.fromkeys(rec, "fp32")) / mixed >= 3.0


def test_wire_dict_validation():
    """Unknown table names and bogus formats fail at construction, not at
    trace time three layers deep."""
    with pytest.raises(ValueError, match="unknown tables"):
        MeshTrainer(_three_table_model(), embed.Adagrad(learning_rate=0.1),
                    mesh=make_mesh(), wire={"nope": "int8"})
    with pytest.raises(ValueError):
        MeshTrainer(_three_table_model(), embed.Adagrad(learning_rate=0.1),
                    mesh=make_mesh(), wire={"a": "int7"})
