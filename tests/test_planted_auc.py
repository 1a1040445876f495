"""Statistical correctness at scale: held-out AUC against a KNOWN optimum.

The reference validates its benchmark models by AUC on real Criteo
(`test/benchmark/criteo_deepctr.py`, `documents/en/benchmark.md:41-56`); a test
battery cannot ship terabytes, so `data.planted_criteo` plants a deterministic
id-conditional signal and `data.planted_logit` IS the generative model's own
scorer — its held-out AUC is the Bayes-optimal target. Any model with a per-id
linear term (LR, W&D, DeepFM's first order) can represent the true scorer, so
after ~10^6 training rows its held-out AUC must land within tolerance of the
oracle's. This replaces eyeballing loss curves with a regression metric: a
sparse-path bug (dropped gradients, mis-routed rows, broken dedup) shows up as
an AUC gap long before it breaks shape checks."""

import numpy as np
import pytest

import jax

import openembedding_tpu as embed
from openembedding_tpu.data import planted_criteo, planted_logit
from openembedding_tpu.model import Trainer
from openembedding_tpu.models import make_deepfm, make_lr, make_wdl
from openembedding_tpu.utils.metrics import auc

VOCAB = 1 << 15
BATCH = 512
STEPS_PER_EPOCH = 200
EPOCHS = 10  # ~1.02M training rows


@pytest.fixture(scope="module")
def heldout():
    batches = list(planted_criteo(BATCH, steps=20, seed=999))
    labels = np.concatenate([b["label"] for b in batches])
    true_logits = np.concatenate(
        [planted_logit(b["sparse"]["categorical"].astype(np.int64), seed=1)
         for b in batches])
    oracle = auc(labels, true_logits)
    # the planted signal itself must be strong and deterministic
    assert 0.82 < oracle < 0.84, oracle
    return batches, labels, oracle


def _train_and_score(model, heldout, epochs=EPOCHS):
    batches_h, labels, _ = heldout
    trainer = Trainer(model, embed.Adam(learning_rate=0.02))
    state = None
    many = trainer.jit_train_many()
    for epoch in range(epochs):
        batches = list(planted_criteo(BATCH, steps=STEPS_PER_EPOCH,
                                      seed=epoch))
        stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)
        if state is None:
            state = trainer.init(batches[0])
        state, m = many(state, stacked)
    assert np.isfinite(np.asarray(m["loss"])).all()
    ev = trainer.jit_eval_step()
    scores = np.concatenate(
        [np.asarray(ev(state, b)["logits"]).reshape(-1) for b in batches_h])
    return auc(labels, scores)


# Tolerances are measured-margin + ~0.005 drift slack, not guesses (the round-4
# review called the old uniform 0.03 loose). Every seed below is fixed, so on
# one platform the achieved AUC is deterministic; measured r5 on the CPU suite
# (oracle 0.8298): lr margin +0.0183, wdl +0.0196, deepfm +0.0308. The slack
# absorbs cross-version/XLA numeric drift (~1e-3), not regressions.
#
# The tight margins are PLATFORM-TUNED (ADVICE r5): they were measured on the
# CPU suite, and reduction order / bf16 matmul behavior differ enough on TPU
# (or any other backend) that the snug deepfm bound can trip without any real
# regression. `_margin` therefore gates the tight bound on the platform it
# was measured on and falls back to a platform-independent floor of >= 0.03
# margin (plus 0.01 cross-platform slack) everywhere else.


def _margin(cpu_tuned: float) -> float:
    if jax.default_backend() == "cpu":
        return cpu_tuned
    return max(cpu_tuned, 0.03) + 0.01


def test_lr_reaches_planted_optimum(heldout):
    _, _, oracle = heldout
    got = _train_and_score(make_lr(vocabulary=VOCAB), heldout)
    assert got > oracle - _margin(0.024), (got, oracle)


def test_wdl_reaches_planted_optimum(heldout):
    _, _, oracle = heldout
    got = _train_and_score(
        make_wdl(vocabulary=VOCAB, dim=8, hidden=(64, 32)), heldout)
    assert got > oracle - _margin(0.025), (got, oracle)


def test_deepfm_reaches_planted_optimum(heldout):
    _, _, oracle = heldout
    got = _train_and_score(
        make_deepfm(vocabulary=VOCAB, dim=8, hidden=(64, 32)), heldout)
    # the FM/deep tower takes longer to stop fighting the linear term;
    # measured 0.7990 vs oracle 0.8298 at 1M rows (r5) — margin 0.0308, so
    # 0.035 is already snug (4.2 millipoints of slack) on CPU
    assert got > oracle - _margin(0.035), (got, oracle)


def test_mesh_trainer_reaches_planted_optimum(heldout):
    """The sharded exchange protocol trains to the same statistical quality:
    8-device mesh, fused dedup+routing, all_to_all pull/push."""
    from openembedding_tpu.parallel import MeshTrainer, make_mesh

    batches_h, labels, oracle = heldout
    trainer = MeshTrainer(make_lr(vocabulary=VOCAB),
                          embed.Adam(learning_rate=0.02), mesh=make_mesh())
    state = None
    many = None
    for epoch in range(EPOCHS):
        batches = list(planted_criteo(BATCH, steps=STEPS_PER_EPOCH,
                                      seed=epoch))
        stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs), *batches)
        if state is None:
            state = trainer.init(batches[0])
            many = trainer.jit_train_many(stacked, state)
        state, m = many(state, stacked)
    assert np.isfinite(np.asarray(m["loss"])).all()
    ev = trainer.jit_eval_step(batches_h[0], state)
    scores = np.concatenate(
        [np.asarray(ev(state, b)["logits"]).reshape(-1) for b in batches_h])
    got = auc(labels, scores)
    # sharded LR trains the same model as test_lr (exchange parity is pinned
    # exactly elsewhere); same data-driven bound as the single-device case
    assert got > oracle - _margin(0.024), (got, oracle)


@pytest.mark.slow  # ~1 min of training; tier-1's timed window can't afford it
def test_mesh_trainer_int8_ef_wire_parity(heldout):
    """Round-13 acceptance: the int8 exchange wire with error feedback (on by
    default for int8 — `MeshTrainer.ef_for`) trains to AUC parity with the
    fp32 wire on the same data. A dim-8 WDL so the per-block quantizer does
    real damage for EF + stochastic rounding to repair (dim-1 LR rows survive
    int8 almost losslessly — sign x max-abs — and would prove nothing).
    Reduced epochs: parity is a DIFFERENCE of two runs on identical batches,
    so it needs far fewer rows than the absolute-AUC bounds above. Marked
    slow: the statistical int8 story is covered in-window by the cheap
    pinned tests in tests/test_wire_inband.py (EF convergence, SR bounds);
    this end-to-end AUC run rides the full (`-m ''`) battery."""
    from openembedding_tpu.parallel import MeshTrainer, make_mesh

    batches_h, labels, _ = heldout
    epochs = 4

    def run(wire):
        trainer = MeshTrainer(
            make_wdl(vocabulary=VOCAB, dim=8, hidden=(64, 32)),
            embed.Adam(learning_rate=0.02), mesh=make_mesh(), wire=wire)
        state = None
        many = None
        for epoch in range(epochs):
            batches = list(planted_criteo(BATCH, steps=STEPS_PER_EPOCH,
                                          seed=epoch))
            stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                             *batches)
            if state is None:
                state = trainer.init(batches[0])
                many = trainer.jit_train_many(stacked, state)
            state, m = many(state, stacked)
        assert np.isfinite(np.asarray(m["loss"])).all()
        if wire == "int8":  # EF attached and actually absorbing residuals
            assert all(ts.ef is not None for ts in state.tables.values())
        ev = trainer.jit_eval_step(batches_h[0], state)
        scores = np.concatenate(
            [np.asarray(ev(state, b)["logits"]).reshape(-1)
             for b in batches_h])
        return auc(labels, scores)

    a_fp32 = run("fp32")
    a_int8 = run("int8")
    # measured on the CPU suite: see the platform note above `_margin`
    assert abs(a_int8 - a_fp32) < _margin(0.01), (a_int8, a_fp32)


@pytest.mark.slow  # two ~1 min training runs; rides the full (`-m ''`) battery
def test_mesh_trainer_dense_wire_int8_parity(heldout):
    """Round-17 acceptance: quantizing the dense ZeRO collectives
    (`dense_wire="int8"`: in-band two-stage grad reduce + bf16-carrier param
    all_gather, per-chunk EF + fp32 masters) trains to AUC parity with the
    lossless round-14 path on the same data. Both runs also quantize the
    sparse exchange so the delta isolates the DENSE wire. Same reduced-epoch
    rationale as the sibling test above: parity is a difference of two runs
    on identical batches."""
    from openembedding_tpu.parallel import MeshTrainer, make_mesh

    batches_h, labels, _ = heldout
    epochs = 4

    def run(dense_wire):
        trainer = MeshTrainer(
            make_wdl(vocabulary=VOCAB, dim=8, hidden=(64, 32)),
            embed.Adam(learning_rate=0.02), mesh=make_mesh(), wire="int8",
            dense_shard=True, dense_wire=dense_wire)
        state = None
        many = None
        for epoch in range(epochs):
            batches = list(planted_criteo(BATCH, steps=STEPS_PER_EPOCH,
                                          seed=epoch))
            stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                             *batches)
            if state is None:
                state = trainer.init(batches[0])
                many = trainer.jit_train_many(stacked, state)
            state, m = many(state, stacked)
        assert np.isfinite(np.asarray(m["loss"])).all()
        ev = trainer.jit_eval_step(batches_h[0], state)
        scores = np.concatenate(
            [np.asarray(ev(state, b)["logits"]).reshape(-1)
             for b in batches_h])
        return auc(labels, scores)

    a_lossless = run(None)
    a_q = run("int8")
    # measured on the CPU suite: see the platform note above `_margin`
    assert abs(a_q - a_lossless) < _margin(0.01), (a_q, a_lossless)
