"""oelint + runtime guards acceptance (ISSUEs 6 and 11).

- every pass catches every `# PLANT:`-marked violation in its corpus file
  (tests/oelint_corpus/), and reports ZERO findings on the clean corpus;
- suppression policy: a reasoned pragma silences a pass, a bare one still
  silences it but is itself flagged;
- the REAL tree is clean under the file-scanning passes (the triage
  satellite: fixes landed, false positives carry reasoned pragmas);
- the hlo-budget pass detects a deliberately added collective and the
  checked-in budget matches the current tree (fused config compiled live);
- implicit-reshard: a deliberately mismatched out_sharding makes GSPMD
  insert an unattributed reshard collective, and the detector fails it;
  explicitly traced collectives always attribute and stay clean;
- utils/guards: assert_no_recompile passes on re-invocation with the same
  shapes, trips on a forced shape change (both plain and pre-jitted forms),
  and trace_counter counts new compilations;
- collective_fingerprint is deterministic, program/shape-sensitive, and
  stays pinned across hot-row refresh, cold-tail migration, and a full
  placement-controller cycle (the SPMD contract as a runtime assertion).
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tools.oelint import run_passes  # noqa: E402
from tools.oelint.core import SourceFile  # noqa: E402
from tools.oelint.passes import (atomicity, condwait,  # noqa: E402
                                 hlo_budget, host_sync, implicit_reshard,
                                 lifecycle, lockset,
                                 metrics as metrics_pass, sharding,
                                 spmd_divergence, trace_hazard)

CORPUS = "tests/oelint_corpus"


def corpus_file(name: str) -> SourceFile:
    return SourceFile(ROOT, f"{CORPUS}/{name}")


def plant_lines(sf: SourceFile) -> set:
    return {i for i, line in enumerate(sf.lines, 1) if "# PLANT:" in line}


def assert_catches_all_plants(pass_mod, sf: SourceFile):
    findings = pass_mod.run([sf], ROOT)
    hit = {f.line for f in findings}
    missed = plant_lines(sf) - hit
    assert not missed, (
        f"{pass_mod.NAME} missed planted violations at "
        f"{sorted(missed)}: " + "\n".join(map(str, findings)))
    assert all(f.pass_name == pass_mod.NAME for f in findings)


# ---------------------------------------------------------------------------
# corpus: every pass fires on its planted violations, none on clean code
# ---------------------------------------------------------------------------


def test_trace_hazard_catches_every_plant():
    assert_catches_all_plants(trace_hazard, corpus_file("trace_hazard_bad.py"))


def test_host_sync_catches_every_plant():
    assert_catches_all_plants(host_sync, corpus_file("host_sync_bad.py"))


def test_lockset_catches_every_plant():
    assert_catches_all_plants(lockset, corpus_file("lockset_bad.py"))


def test_atomicity_catches_every_plant():
    assert_catches_all_plants(atomicity, corpus_file("atomicity_bad.py"))


def test_atomicity_clean_idioms_stay_clean():
    """check+act under one critical section, re-check inside the lock, and
    Condition aliases are never flagged: exactly the plants fire."""
    sf = corpus_file("atomicity_bad.py")
    findings = atomicity.run([sf], ROOT)
    assert {f.line for f in findings} == plant_lines(sf), \
        "\n".join(map(str, findings))


def test_condwait_catches_every_plant():
    assert_catches_all_plants(condwait, corpus_file("condwait_bad.py"))


def test_condwait_clean_idioms_stay_clean():
    """while-predicate waits (timed included), wait_for, locked notify, the
    underlying-lock alias, and Event.wait are never flagged."""
    sf = corpus_file("condwait_bad.py")
    findings = condwait.run([sf], ROOT)
    assert {f.line for f in findings} == plant_lines(sf), \
        "\n".join(map(str, findings))


def test_lifecycle_catches_every_plant():
    assert_catches_all_plants(lifecycle, corpus_file("lifecycle_bad.py"))


def test_lifecycle_clean_idioms_stay_clean():
    """tuple-swap join, join via a stop helper, and returned/handed-off/
    locally-joined threads are never flagged."""
    sf = corpus_file("lifecycle_bad.py")
    findings = lifecycle.run([sf], ROOT)
    assert {f.line for f in findings} == plant_lines(sf), \
        "\n".join(map(str, findings))


def test_metrics_catches_every_plant():
    assert_catches_all_plants(metrics_pass, corpus_file("metrics_bad.py"))


def test_sharding_catches_every_plant():
    assert_catches_all_plants(sharding, corpus_file("sharding_bad.py"))


def test_sharding_reference_sites_stay_clean():
    """The registry's agreeing/reference spellings are never flagged — only
    the disagreeing minority sites are."""
    sf = corpus_file("sharding_bad.py")
    findings = sharding.run([sf], ROOT)
    assert {f.line for f in findings} == plant_lines(sf), \
        "\n".join(map(str, findings))


def test_spmd_divergence_catches_every_plant():
    assert_catches_all_plants(spmd_divergence,
                              corpus_file("spmd_divergence_bad.py"))


def test_spmd_divergence_uniform_controls_stay_clean():
    """process_count branches, step-driven cadences, and collective-free
    process-0 work are uniform: exactly the plants fire, nothing else."""
    sf = corpus_file("spmd_divergence_bad.py")
    findings = spmd_divergence.run([sf], ROOT)
    assert {f.line for f in findings} == plant_lines(sf), \
        "\n".join(map(str, findings))


def test_clean_corpus_is_clean():
    sf = corpus_file("clean.py")
    for pass_mod in (trace_hazard, host_sync, lockset, atomicity, condwait,
                     lifecycle, metrics_pass, sharding, spmd_divergence):
        findings = pass_mod.run([sf], ROOT)
        assert not findings, (pass_mod.NAME, list(map(str, findings)))
    assert sf.bare_suppressions() == []


def test_suppression_needs_a_reason():
    sf = corpus_file("suppress_bad.py")
    # both hazards are suppressed (reasoned or not): the pass stays silent
    assert trace_hazard.run([sf], ROOT) == []
    # ...but the reasonless pragma is itself a finding
    bare = sf.bare_suppressions()
    assert len(bare) == 1
    assert "bare suppression" in bare[0].message
    assert bare[0].pass_name == "suppression"


def test_tree_is_clean_under_file_passes():
    """The triage satellite's regression pin: the real tree stays green
    under every file-scanning pass (real findings fixed, false positives
    carry reasoned pragmas — zero bare suppressions anywhere)."""
    findings, _ = run_passes(["trace-hazard", "host-sync", "lockset",
                              "atomicity", "cond-wait", "thread-lifecycle",
                              "metrics", "sharding", "spmd-divergence"])
    assert findings == [], "\n".join(map(str, findings))


# ---------------------------------------------------------------------------
# hlo-budget: the compiled collective set is pinned per config
# ---------------------------------------------------------------------------


def test_hlo_budget_compare_reports_readable_diffs():
    budget = {"configs": {"fused_fp32": {"all_to_all": 3, "all_reduce": 17,
                                         "wire_bytes_per_step": 32256}}}
    same = {"fused_fp32": {"all_to_all": 3, "all_reduce": 17,
                           "wire_bytes_per_step": 32256}}
    assert hlo_budget.compare(same, budget) == []
    worse = {"fused_fp32": {"all_to_all": 4, "all_reduce": 17,
                            "wire_bytes_per_step": 40000}}
    msgs = [f.message for f in hlo_budget.compare(worse, budget)]
    assert any("all-to-all" in m and "ADDED" in m for m in msgs)
    assert any("bytes/step grew" in m for m in msgs)
    # a missing budget file is itself a finding, not a silent pass
    assert hlo_budget.compare(same, None)
    # an unknown config demands a budget regen
    extra = {"new_cfg": {"all_to_all": 1}}
    assert any("not in the checked-in budget" in f.message
               for f in hlo_budget.compare(extra, budget))


def test_hlo_budget_matches_tree_and_detects_planted_collective():
    """Acceptance: the checked-in budget matches the CURRENT tree for the
    fused config (fresh clone -> `make lint` green), and a deliberately
    added collective on that pinned path is detected."""
    import jax

    budget = hlo_budget.load_budget(ROOT)
    assert budget is not None, "tools/oelint/hlo_budget.json not checked in"
    cfg = next(c for c in hlo_budget.CONFIGS if c["name"] == "fused_fp32")

    trainer, batch = hlo_budget.make_trainer(cfg)
    clean = {"fused_fp32": hlo_budget.measure_trainer(trainer, batch)}
    assert hlo_budget.compare(clean, budget) == [], (
        "checked-in budget is stale vs the tree: run "
        "`python -m tools.oelint --update-budget`")

    # plant one extra collective on the pinned path: an extra pmean of the
    # loss is numerically inert (loss is replicated) but compiles to one
    # more all-reduce — exactly the regression class the pass exists for
    planted, batch2 = hlo_budget.make_trainer(cfg)
    orig = planted.reduce_metrics

    def with_extra_collective(m):
        out = orig(m)
        out["loss"] = jax.lax.pmean(out["loss"], planted.axis)
        return out

    planted.reduce_metrics = with_extra_collective
    measured = {"fused_fp32": hlo_budget.measure_trainer(planted, batch2)}
    msgs = [f.message for f in hlo_budget.compare(measured, budget)]
    assert any("all-reduce" in m and "ADDED" in m for m in msgs), msgs


def test_hlo_budget_covers_acceptance_matrix():
    """The checked-in budget pins fused-group, hot-on/off and all three wire
    modes (the ISSUE 6 acceptance list) — by name."""
    import re

    budget = hlo_budget.load_budget(ROOT)
    names = set(budget["configs"])
    assert {"fused_fp32", "fused_bf16", "fused_int8",
            "fused_fp32_hot"} <= names
    # and the pins are non-degenerate: a mixed-format dim splits its group,
    # hot adds all-reduces, quantized wire ships fewer bytes
    cfgs = budget["configs"]
    assert cfgs["fused_fp32"]["all_to_all"] < \
        cfgs["fused_mixed_wire"]["all_to_all"]

    def traced_all_reduces(name):
        # in the module as traced: the compiler's combiner merges all-reduces
        # (the budget's optimised count reads 2 with or without a hot cache)
        cfg = next(c for c in hlo_budget.CONFIGS if c["name"] == name)
        trainer, batch = hlo_budget.make_trainer(cfg)
        state = trainer.init(batch)
        text = trainer.jit_train_step(batch, state).lower(
            state, batch).as_text()
        return len(re.findall(r"stablehlo\.all_reduce", text))

    assert traced_all_reduces("fused_fp32_hot") > \
        traced_all_reduces("fused_fp32")
    assert cfgs["fused_int8"]["wire_bytes_per_step"] < \
        cfgs["fused_bf16"]["wire_bytes_per_step"] < \
        cfgs["fused_fp32"]["wire_bytes_per_step"]


# ---------------------------------------------------------------------------
# implicit-reshard: GSPMD-inserted collectives fail lint
# ---------------------------------------------------------------------------


def test_implicit_reshard_fires_on_planted_gspmd_reshard():
    """Acceptance: a deliberately mismatched out_sharding on a compiled fn
    makes GSPMD insert a reshard collective with NO traced-op attribution —
    and the detector fails lint on it, budget-independent."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from openembedding_tpu.parallel import make_mesh
    mesh = make_mesh()
    axis = mesh.axis_names[0]
    row = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    # input arrives row-sharded, output is demanded replicated: the program
    # asks for NO collective, GSPMD must insert the all-gather itself
    f = jax.jit(lambda x: x * 2.0, in_shardings=(row,), out_shardings=rep)
    text = f.lower(jnp.zeros((8, 4))).compile().as_text()
    planted = hlo_budget.unattributed_collectives(text)
    assert planted, "expected a GSPMD-inserted reshard collective"
    assert all(kind in hlo_budget.COLLECTIVES for kind, _ in planted)

    measured = {"planted_cfg": {
        "unattributed_collectives": len(planted),
        "_unattributed_detail": "; ".join(f"{k} <- {a}"
                                          for k, a in planted)}}
    msgs = [f.message for f in implicit_reshard.findings_for(measured)]
    assert msgs and "GSPMD inserted a reshard" in msgs[0], msgs
    assert all(f.pass_name == implicit_reshard.NAME
               for f in implicit_reshard.findings_for(measured))


def test_implicit_reshard_clean_on_attributed_collectives():
    """Explicitly traced collectives carry their primitive in op_name and
    never count as unattributed (verified live on a compiled psum)."""
    import jax
    import jax.numpy as jnp

    from openembedding_tpu.parallel import make_mesh
    from jax.sharding import PartitionSpec as P
    mesh = make_mesh()
    axis = mesh.axis_names[0]
    f = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, axis), mesh=mesh,
                              in_specs=P(axis), out_specs=P()))
    text = f.lower(jnp.zeros((8, 4))).compile().as_text()
    assert hlo_budget.count_collectives(text)["all_reduce"] >= 1
    assert hlo_budget.unattributed_collectives(text) == []
    assert implicit_reshard.findings_for(
        {"cfg": {"unattributed_collectives": 0}}) == []


# ---------------------------------------------------------------------------
# utils/guards: the never-re-jit rule as a runtime assertion
# ---------------------------------------------------------------------------


def test_assert_no_recompile_plain_callable():
    import jax.numpy as jnp

    from openembedding_tpu.utils.guards import (RecompileError,
                                                assert_no_recompile)
    calls = []

    @assert_no_recompile
    def fn(x):
        calls.append(1)
        return x * 2

    np.testing.assert_array_equal(fn(jnp.ones((4,))), 2 * np.ones(4))
    fn(jnp.ones((4,)))  # same shape: cached, no retrace
    assert fn.trace_count() == 1
    with pytest.raises(RecompileError, match="traced 2 times"):
        fn(jnp.ones((5,)))  # forced shape change


def test_assert_no_recompile_prejitted_fn():
    import jax
    import jax.numpy as jnp

    from openembedding_tpu.utils.guards import (RecompileError,
                                                assert_no_recompile)
    step = jax.jit(lambda x: x + 1)
    guarded = assert_no_recompile(step, label="step")
    guarded(jnp.ones((2, 3)))
    guarded(jnp.ones((2, 3)))  # re-invocation, same shapes: fine
    with pytest.raises(RecompileError, match="new programs"):
        guarded(jnp.ones((2, 4)))


def test_assert_no_recompile_multi_mode_budget():
    import jax.numpy as jnp

    from openembedding_tpu.utils.guards import (RecompileError,
                                                assert_no_recompile)
    fn = assert_no_recompile(lambda x: x, max_traces=2)
    fn(jnp.ones((1,)))
    fn(jnp.ones((2,)))  # second mode: inside the budget
    with pytest.raises(RecompileError):
        fn(jnp.ones((3,)))


def test_trace_counter_counts_new_compilations():
    import jax
    import jax.numpy as jnp

    from openembedding_tpu.utils.guards import trace_counter
    fn = jax.jit(lambda x: x - 1)
    fn(jnp.ones((2,)))  # warmup outside the window
    with trace_counter(fn) as tc:
        fn(jnp.ones((2,)))
        assert tc.new_traces == 0
        fn(jnp.ones((9,)))
        assert tc.new_traces == 1
    assert tc.new_traces == 1  # still readable after exit


# ---------------------------------------------------------------------------
# utils/guards: collective_fingerprint — the SPMD contract as a runtime pin
# ---------------------------------------------------------------------------


def _psum_and_pmax_fns():
    import jax
    from jax.sharding import PartitionSpec as P

    from openembedding_tpu.parallel import make_mesh
    mesh = make_mesh()
    axis = mesh.axis_names[0]
    mk = lambda op: jax.shard_map(  # noqa: E731
        lambda x: op(x, axis), mesh=mesh, in_specs=P(axis), out_specs=P())
    return mk(jax.lax.psum), mk(jax.lax.pmax)


def test_collective_fingerprint_deterministic_and_program_sensitive():
    import jax.numpy as jnp

    from openembedding_tpu.utils.guards import (collective_fingerprint,
                                                collective_sequence)
    sum_fn, max_fn = _psum_and_pmax_fns()
    x = jnp.ones((8, 4))
    fp = collective_fingerprint(sum_fn, x)
    assert fp == collective_fingerprint(sum_fn, x)   # pure function of trace
    assert fp != collective_fingerprint(max_fn, x)   # different program
    assert fp != collective_fingerprint(sum_fn, jnp.ones((16, 4)))  # shapes
    seq = collective_sequence(sum_fn, x)
    assert len(seq) == 1 and "psum" in str(seq[0]), seq


def test_assert_collective_fingerprint_pass_and_trip():
    import jax.numpy as jnp

    from openembedding_tpu.utils.guards import (
        CollectiveMismatchError, assert_collective_fingerprint,
        collective_fingerprint)
    sum_fn, max_fn = _psum_and_pmax_fns()
    x = jnp.ones((8, 4))
    pin = collective_fingerprint(sum_fn, x)
    assert_collective_fingerprint(sum_fn, pin, x, label="unit")  # no raise
    with pytest.raises(CollectiveMismatchError) as e:
        assert_collective_fingerprint(max_fn, pin, x, label="unit")
    assert "pmax" in str(e.value)  # the message carries the traced sequence


def test_collective_fingerprint_survives_refresh_and_migration():
    """Acceptance (1/2): hot-row refresh and cold-tail migration on the
    pinned fused placement config are content-only — the traced collective
    sequence of the SAME step function is byte-identical after both."""
    from openembedding_tpu.utils.guards import (assert_collective_fingerprint,
                                                collective_fingerprint)
    cfg = next(c for c in hlo_budget.CONFIGS
               if c["name"] == "fused_fp32_placement")
    tr, batch = hlo_budget.make_trainer(cfg)
    state = tr.init(batch)
    step = tr.jit_train_step(batch, state)
    pin = collective_fingerprint(step, state, batch)

    state, _ = step(state, batch)
    state = tr.refresh_hot_rows(
        state, hot_ids={"a": np.arange(32, dtype=np.int64)})
    assert_collective_fingerprint(step, pin, state, batch,
                                  label="post_refresh")
    state = tr.migrate_rows(
        state, moves={"a": (np.array([97, 193], np.int64),
                            np.array([3, 5], np.int64))})
    assert_collective_fingerprint(step, pin, state, batch,
                                  label="post_migration")


def test_collective_fingerprint_survives_placement_controller_cycle():
    """Acceptance (2/2): a full self-driving placement cycle — prime, then
    controller-decided refreshes/migrations under drifting Zipf traffic —
    never changes the traced collective sequence of the step it drives."""
    import flax.linen as nn
    import jax.numpy as jnp

    import openembedding_tpu as embed
    from openembedding_tpu.model import EmbeddingModel
    from openembedding_tpu.parallel import MeshTrainer, make_mesh
    from openembedding_tpu.placement import (PlacementController,
                                             PlacementPolicy)
    from openembedding_tpu.placement.policy import row_bytes
    from openembedding_tpu.utils.guards import (assert_collective_fingerprint,
                                                collective_fingerprint)
    from openembedding_tpu.utils.sketch import SkewMonitor

    class Tower(nn.Module):
        @nn.compact
        def __call__(self, embedded, dense):
            bias = self.param("bias", nn.initializers.zeros, (1,),
                              jnp.float32)
            return jnp.sum(embedded["a"].astype(jnp.float32),
                           axis=(1, 2)) + bias[0]

    S, B, VOCAB = 8, 32, 1 << 10
    model = EmbeddingModel(Tower(), [embed.Embedding(VOCAB, 8, name="a")])
    rng = np.random.default_rng(3)
    # heavy pool homed on one shard, rotated to another mid-run: forces the
    # controller through refresh AND migration decisions (test_placement's
    # drift pattern, shortened — efficacy is pinned there, not here)
    pool_a = (np.arange(16) * S + 5).astype(np.int64)
    pool_b = (np.arange(16) * S + 3).astype(np.int64)
    batches = []
    for i in range(12):
        pool = pool_a if i < 6 else pool_b
        ids = rng.integers(0, VOCAB, (B, 8)).astype(np.int64)
        ids[:, :4] = pool[rng.integers(0, 16, (B, 4))]
        batches.append({"sparse": {"a": ids.astype(np.int32)},
                        "label": rng.integers(0, 2, (B,)).astype(np.float32)})

    mon = SkewMonitor(k=64, sync=True, decay=0.85)
    tr = MeshTrainer(model, embed.Adagrad(learning_rate=0.1),
                     mesh=make_mesh(), wire="fp32")
    policy = PlacementPolicy(8 * row_bytes(8, 1), mig_rows=32,
                             refresh_cooldown_steps=2, imbalance_target=1.05)
    ctrl = PlacementController(tr, policy, monitor=mon, interval_steps=2)
    for b in batches[:3]:  # warm the sketches so prime() can size
        mon.observe("a", b["sparse"]["a"])
    state = tr.init(batches[0])
    state = ctrl.prime(state)  # the one shape-changing moment — pin AFTER
    step = tr.jit_train_step(batches[0], state)
    pin = collective_fingerprint(step, state, batches[0])

    for i, b in enumerate(batches):
        mon.observe("a", b["sparse"]["a"])
        state, _ = step(state, b)
        state = ctrl.on_step(state, step=i + 1)

    st = ctrl.status()
    actuated = (st["migrations_applied"] >= 1
                or any(v > 0 for v in st["last_refresh_step"].values()))
    assert actuated, st  # the cycle must not be vacuous
    assert_collective_fingerprint(step, pin, state, batches[0],
                                  label="placement_cycle")
