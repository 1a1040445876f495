"""Sparse embedding optimizers as fused row-update functions.

Counterpart of the reference's server-side optimizer family
(`variable/EmbeddingOptimizer.h`): default(SGD, stateless), sgd(momentum/nesterov),
adagrad, adadelta, adam (per-row beta^t pair), adamax (per-row beta1^t), ftrl (full
l1/l2/shrinkage/beta and non--0.5 lr_power path), rmsprop, and the deterministic `test`
optimizer used by the self-checking cluster tests.

Semantics preserved exactly (these are TF-Keras formulas — the reference matches TF so
that PS-trained models equal GPU-trained ones; see `test/optimizer_test.py`):

- Gradients of duplicate ids are **summed** (not averaged) before the update, and the
  optimizer is applied **once per unique id**; `count` (number of duplicate occurrences,
  summed over workers) is passed but only the `test` optimizer divides by it
  (reference: `MpscGradientReducer.h:26-53`, `EmbeddingOptimizerVariable.h:273-297`).
- Adam/Adamax bias-correction powers beta^t are **per-row** state advanced only when the
  row is touched (reference: `EmbeddingOptimizer.h:156-181,199-220` keeps them in the
  row's state block).

On TPU the update runs as one XLA fusion over the block of unique rows
gathered from the owning shard: `apply(weights, slots, grads, counts)` where rows with
`counts == 0` (padding of the static-capacity unique buffer) are left bit-identical.

Each optimizer is a hashable dataclass (static under jit) registered by category name,
with Keras-optimizer translation mirroring `tensorflow/exb.py:66-86`.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Tuple, Type

import jax
import jax.numpy as jnp

Slots = Dict[str, jax.Array]

_REGISTRY: Dict[str, Type["SparseOptimizer"]] = {}


def _register(cls):
    _REGISTRY[cls.category] = cls
    return cls


def _masked(mask, new, old):
    """Rows not touched this step stay bit-identical (padding rows of the static
    unique-id buffer and rows whose count is 0)."""
    return jnp.where(mask.reshape((-1,) + (1,) * (new.ndim - 1)), new, old)


@dataclasses.dataclass(frozen=True)
class SparseOptimizer:
    """Base: subclass provides slot layout + fused row update.

    `slot_shapes(dim)` -> {name: row_width}; slots are (num_rows, width) arrays so they
    shard/checkpoint exactly like the weights (reference keeps them interleaved per row,
    `EmbeddingOptimizerVariable.h`; separate arrays are the XLA-friendly layout).
    """

    category = ""

    def slot_shapes(self, dim: int) -> Dict[str, int]:
        return {}

    def slot_init(self, name: str) -> float:
        return 0.0

    def init_slots(self, num_rows: int, dim: int, dtype=jnp.float32) -> Slots:
        """train_init for every row up front (reference runs train_init lazily when a
        row is first committed, `EmbeddingOptimizerVariable.h:273-297`; init values are
        deterministic constants so eager init is equivalent).

        Slots are always float32 even for bf16 tables: accumulators and the per-row
        beta^t powers are numerically unusable in bf16 (0.999 rounds to 1.0). The
        `dtype` arg is honored only if it is at least f32-wide.
        """
        dtype = jnp.float32 if jnp.dtype(dtype).itemsize < 4 else dtype
        return {
            name: jnp.full((num_rows, width), self.slot_init(name), dtype=dtype)
            for name, width in self.slot_shapes(dim).items()
        }

    def apply(self, weights: jax.Array, slots: Slots, grads: jax.Array,
              counts: jax.Array) -> Tuple[jax.Array, Slots]:
        """weights/grads: (n, dim); counts: (n,) int — summed duplicate multiplicity,
        0 = padding row (no-op). Returns (new_weights, new_slots)."""
        raise NotImplementedError

    def to_config(self) -> dict:
        d = dataclasses.asdict(self)
        d["category"] = self.category
        return d


@_register
@dataclasses.dataclass(frozen=True)
class Default(SparseOptimizer):
    """Stateless SGD; lr=0 means pull-only serving tables
    (reference: EmbeddingDefaultOptimizer, `EmbeddingOptimizer.h:49-72`)."""

    category = "default"
    learning_rate: float = 0.0

    def apply(self, weights, slots, grads, counts):
        mask = counts > 0
        new_w = weights - self.learning_rate * grads
        return _masked(mask, new_w, weights), slots


@_register
@dataclasses.dataclass(frozen=True)
class SGD(SparseOptimizer):
    """SGD with momentum/nesterov. Keras semantics: moment = moment*mu + lr*grad
    (reference: EmbeddingSGDOptimizer, `EmbeddingOptimizer.h:332-363`; note the
    reference allocates the moment slot even for mu=0)."""

    category = "sgd"
    learning_rate: float = 0.01
    momentum: float = 0.0
    nesterov: bool = False

    def slot_shapes(self, dim):
        return {"moment": dim}

    def apply(self, weights, slots, grads, counts):
        mask = counts > 0
        moment = slots["moment"] * self.momentum + self.learning_rate * grads
        if self.nesterov:
            new_w = weights - (moment * self.momentum + self.learning_rate * grads)
        else:
            new_w = weights - moment
        return (_masked(mask, new_w, weights),
                {"moment": _masked(mask, moment, slots["moment"])})


def Momentum(learning_rate=0.01, momentum=0.9, nesterov=False) -> SGD:
    return SGD(learning_rate=learning_rate, momentum=momentum, nesterov=nesterov)


@_register
@dataclasses.dataclass(frozen=True)
class Adagrad(SparseOptimizer):
    """accum += g^2; w -= lr * g / (sqrt(accum) + eps)
    (reference: EmbeddingAdagradOptimizer, `EmbeddingOptimizer.h:117-144`)."""

    category = "adagrad"
    learning_rate: float = 0.001
    initial_accumulator_value: float = 0.1
    epsilon: float = 1e-7

    def slot_shapes(self, dim):
        return {"accum": dim}

    def slot_init(self, name):
        return self.initial_accumulator_value

    def apply(self, weights, slots, grads, counts):
        mask = counts > 0
        accum = slots["accum"] + grads * grads
        new_w = weights - self.learning_rate * grads / (jnp.sqrt(accum) + self.epsilon)
        return (_masked(mask, new_w, weights),
                {"accum": _masked(mask, accum, slots["accum"])})


@_register
@dataclasses.dataclass(frozen=True)
class Adadelta(SparseOptimizer):
    """(reference: EmbeddingAdadeltaOptimizer, `EmbeddingOptimizer.h:76-113`)."""

    category = "adadelta"
    learning_rate: float = 0.001
    rho: float = 0.95
    epsilon: float = 1e-7

    def slot_shapes(self, dim):
        return {"accum": dim, "accum_update": dim}

    def apply(self, weights, slots, grads, counts):
        mask = counts > 0
        accum = slots["accum"] * self.rho + grads * grads * (1 - self.rho)
        update = grads * jnp.sqrt(slots["accum_update"] + self.epsilon) / jnp.sqrt(accum + self.epsilon)
        accum_update = slots["accum_update"] * self.rho + update * update * (1 - self.rho)
        new_w = weights - self.learning_rate * update
        return (_masked(mask, new_w, weights),
                {"accum": _masked(mask, accum, slots["accum"]),
                 "accum_update": _masked(mask, accum_update, slots["accum_update"])})


@_register
@dataclasses.dataclass(frozen=True)
class Adam(SparseOptimizer):
    """Keras Adam with per-row beta^t: lr_t = lr*sqrt(1-b2^t)/(1-b1^t);
    w -= lr_t * m / (sqrt(v) + eps). beta powers advance only on touched rows
    (reference: EmbeddingAdamOptimizer, `EmbeddingOptimizer.h:148-187`)."""

    category = "adam"
    learning_rate: float = 0.001
    beta_1: float = 0.9
    beta_2: float = 0.999
    epsilon: float = 1e-7

    def slot_shapes(self, dim):
        return {"m": dim, "v": dim, "beta_1_t": 1, "beta_2_t": 1}

    def slot_init(self, name):
        return 1.0 if name in ("beta_1_t", "beta_2_t") else 0.0

    def apply(self, weights, slots, grads, counts):
        mask = counts > 0
        b1t = slots["beta_1_t"] * self.beta_1
        b2t = slots["beta_2_t"] * self.beta_2
        lr_t = self.learning_rate * jnp.sqrt(1 - b2t) / (1 - b1t)  # (n, 1)
        m = slots["m"] * self.beta_1 + grads * (1 - self.beta_1)
        v = slots["v"] * self.beta_2 + grads * grads * (1 - self.beta_2)
        new_w = weights - lr_t * m / (jnp.sqrt(v) + self.epsilon)
        return (_masked(mask, new_w, weights),
                {"m": _masked(mask, m, slots["m"]),
                 "v": _masked(mask, v, slots["v"]),
                 "beta_1_t": _masked(mask, b1t, slots["beta_1_t"]),
                 "beta_2_t": _masked(mask, b2t, slots["beta_2_t"])})


@_register
@dataclasses.dataclass(frozen=True)
class Adamax(SparseOptimizer):
    """(reference: EmbeddingAdamaxOptimizer, `EmbeddingOptimizer.h:191-226`)."""

    category = "adamax"
    learning_rate: float = 0.001
    beta_1: float = 0.9
    beta_2: float = 0.999
    epsilon: float = 1e-7

    def slot_shapes(self, dim):
        return {"m": dim, "v": dim, "beta_1_t": 1}

    def slot_init(self, name):
        return 1.0 if name == "beta_1_t" else 0.0

    def apply(self, weights, slots, grads, counts):
        mask = counts > 0
        b1t = slots["beta_1_t"] * self.beta_1
        lr_t = self.learning_rate / (1 - b1t)  # (n, 1)
        m = slots["m"] * self.beta_1 + grads * (1 - self.beta_1)
        v = jnp.maximum(jnp.abs(grads), slots["v"] * self.beta_2)
        new_w = weights - lr_t * m / (v + self.epsilon)
        return (_masked(mask, new_w, weights),
                {"m": _masked(mask, m, slots["m"]),
                 "v": _masked(mask, v, slots["v"]),
                 "beta_1_t": _masked(mask, b1t, slots["beta_1_t"])})


@_register
@dataclasses.dataclass(frozen=True)
class Ftrl(SparseOptimizer):
    """Full TF FTRL: l1/l2, l2-shrinkage, beta, and the general lr_power != -0.5 path.
    Note accum_new adds grad^2 (not shrinkage-adjusted g^2), matching TF and the
    reference (reference: EmbeddingFtrlOptimizer, `EmbeddingOptimizer.h:230-293`)."""

    category = "ftrl"
    learning_rate: float = 0.001
    initial_accumulator_value: float = 0.1
    l1_regularization_strength: float = 0.0
    l2_regularization_strength: float = 0.0
    l2_shrinkage_regularization_strength: float = 0.0
    learning_rate_power: float = -0.5
    beta: float = 0.0

    def slot_shapes(self, dim):
        return {"accum": dim, "linear": dim}

    def slot_init(self, name):
        return self.initial_accumulator_value if name == "accum" else 0.0

    def apply(self, weights, slots, grads, counts):
        mask = counts > 0
        accum, linear = slots["accum"], slots["linear"]
        l1 = self.l1_regularization_strength
        adjusted_l2 = self.l2_regularization_strength + self.beta / self.learning_rate / 2
        g = grads + 2 * self.l2_shrinkage_regularization_strength * weights
        accum_new = accum + grads * grads
        if self.learning_rate_power == -0.5:
            sigma = (jnp.sqrt(accum_new) - jnp.sqrt(accum)) / self.learning_rate
            quadratic = jnp.sqrt(accum_new) / self.learning_rate + 2 * adjusted_l2
        else:
            p = -self.learning_rate_power
            sigma = (jnp.power(accum_new, p) - jnp.power(accum, p)) / self.learning_rate
            quadratic = jnp.power(accum_new, p) / self.learning_rate + 2 * adjusted_l2
        linear_new = linear + g - sigma * weights
        l1_reg_adjust = jnp.clip(linear_new, -l1, l1)
        new_w = (l1_reg_adjust - linear_new) / quadratic
        return (_masked(mask, new_w, weights),
                {"accum": _masked(mask, accum_new, accum),
                 "linear": _masked(mask, linear_new, linear)})


@_register
@dataclasses.dataclass(frozen=True)
class RMSprop(SparseOptimizer):
    """(reference: EmbeddingRMSpropOptimizer, `EmbeddingOptimizer.h:297-328`;
    centered/amsgrad rejected by the translation layer, `exb.py:66-86`)."""

    category = "rmsprop"
    learning_rate: float = 0.001
    rho: float = 0.9
    momentum: float = 0.0
    epsilon: float = 1e-7

    def slot_shapes(self, dim):
        return {"accum": dim, "moment": dim}

    def apply(self, weights, slots, grads, counts):
        mask = counts > 0
        accum = slots["accum"] * self.rho + grads * grads * (1 - self.rho)
        moment = (slots["moment"] * self.momentum
                  + self.learning_rate * grads / jnp.sqrt(accum + self.epsilon))
        new_w = weights - moment
        return (_masked(mask, new_w, weights),
                {"accum": _masked(mask, accum, slots["accum"]),
                 "moment": _masked(mask, moment, slots["moment"])})


@_register
@dataclasses.dataclass(frozen=True)
class TestOptimizer(SparseOptimizer):
    """Deterministic flip-state optimizer for the self-checking cluster tests; the only
    one that divides by count (reference: EmbeddingTestOptimizer,
    `EmbeddingOptimizer.h:366-390`, used by `entry/c_api_test.h:32-154`)."""

    category = "test"
    learning_rate: float = 0.1
    flip: float = 10000.0
    init: float = 0.0

    def slot_shapes(self, dim):
        return {"flip_state": 1}

    def slot_init(self, name):
        return self.init

    def apply(self, weights, slots, grads, counts):
        mask = counts > 0
        state = self.flip - slots["flip_state"]  # (n, 1)
        safe_counts = jnp.maximum(counts, 1).astype(weights.dtype)[:, None]
        new_w = weights + self.learning_rate * grads / safe_counts + state
        return (_masked(mask, new_w, weights),
                {"flip_state": _masked(mask, state, slots["flip_state"])})


def make_optimizer(config: dict) -> SparseOptimizer:
    """Build from {category, **params} (reference: Factory registration,
    `EmbeddingVariable.cpp:173-254`)."""
    config = dict(config)
    category = config.pop("category")
    cls = _REGISTRY.get(category)
    if cls is None:
        raise ValueError(f"unknown optimizer category {category!r}")
    return cls(**config)


def from_keras(optimizer) -> SparseOptimizer:
    """Translate a Keras optimizer to the sparse equivalent, rejecting the same
    unsupported features (amsgrad, centered, decay) as the reference
    (`tensorflow/exb.py:66-86`)."""
    cfg = optimizer.get_config()
    name = cfg.get("name", type(optimizer).__name__).lower()
    if cfg.get("amsgrad"):
        raise ValueError("amsgrad not supported")
    if cfg.get("centered"):
        raise ValueError("centered rmsprop not supported")
    for decay_key in ("decay", "weight_decay"):
        if cfg.get(decay_key):
            raise ValueError(f"{decay_key} not supported")
    lr = float(cfg.get("learning_rate", 0.001))
    if name == "sgd":
        return SGD(learning_rate=lr, momentum=float(cfg.get("momentum", 0.0)),
                   nesterov=bool(cfg.get("nesterov", False)))
    if name == "adagrad":
        return Adagrad(learning_rate=lr,
                       initial_accumulator_value=float(cfg.get("initial_accumulator_value", 0.1)),
                       epsilon=float(cfg.get("epsilon", 1e-7)))
    if name == "adadelta":
        return Adadelta(learning_rate=lr, rho=float(cfg.get("rho", 0.95)),
                        epsilon=float(cfg.get("epsilon", 1e-7)))
    if name == "adam":
        return Adam(learning_rate=lr, beta_1=float(cfg.get("beta_1", 0.9)),
                    beta_2=float(cfg.get("beta_2", 0.999)),
                    epsilon=float(cfg.get("epsilon", 1e-7)))
    if name == "adamax":
        return Adamax(learning_rate=lr, beta_1=float(cfg.get("beta_1", 0.9)),
                      beta_2=float(cfg.get("beta_2", 0.999)),
                      epsilon=float(cfg.get("epsilon", 1e-7)))
    if name == "ftrl":
        return Ftrl(learning_rate=lr,
                    initial_accumulator_value=float(cfg.get("initial_accumulator_value", 0.1)),
                    l1_regularization_strength=float(cfg.get("l1_regularization_strength", 0.0)),
                    l2_regularization_strength=float(cfg.get("l2_regularization_strength", 0.0)),
                    l2_shrinkage_regularization_strength=float(
                        cfg.get("l2_shrinkage_regularization_strength", 0.0)),
                    learning_rate_power=float(cfg.get("learning_rate_power", -0.5)),
                    beta=float(cfg.get("beta", 0.0)))
    if name == "rmsprop":
        return RMSprop(learning_rate=lr, rho=float(cfg.get("rho", 0.9)),
                       momentum=float(cfg.get("momentum", 0.0)),
                       epsilon=float(cfg.get("epsilon", 1e-7)))
    raise ValueError(f"unsupported optimizer {name!r}")
