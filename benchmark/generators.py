"""Traffic generation for training cells: the benchmark's own copy of the
program's `data/criteo.py: synthetic_criteo` (Zipf ids by inverse CDF, FNV field
hashing, labels from a fixed random linear model), kept here so that a later PR
cannot change the traffic it is measured on.

One general generator reads a traffic file's parameters; nothing here knows a
cell by name.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

_FNV_OFFSET = np.uint64(0xCBF29CE484222325)
_FNV_PRIME = np.uint64(0x100000001B3)


def hash_category(token_hash: np.ndarray, field: np.ndarray, id_space: int) -> np.ndarray:
    """(token hash, field index) -> folded id in [0, id_space); salted by field."""
    h = (token_hash.astype(np.uint64) ^ _FNV_OFFSET) * _FNV_PRIME
    h ^= field.astype(np.uint64) + np.uint64(0x9E3779B97F4A7C15)
    h *= _FNV_PRIME
    h &= np.uint64(0x7FFFFFFFFFFFFFFF)
    return (h % np.uint64(id_space)).astype(np.int64)


def zipf_criteo_batches(*, batch_size: int, steps: int, id_space: int, seed: int,
                        alpha: float, num_fields: int, dense_dim: int,
                        feature: str = "categorical") -> List[Dict]:
    """`steps` distinct batches: ids (B, F) int32 Zipf(alpha) over `id_space`,
    dense (B, D) f32 standard normal, label (B,) f32 Bernoulli of a linear model."""
    rng = np.random.default_rng(seed)
    w_dense = rng.normal(size=(dense_dim,)).astype(np.float32) * 0.3
    fields = np.broadcast_to(np.arange(num_fields, dtype=np.uint64),
                             (batch_size, num_fields))
    out = []
    for _ in range(steps):
        u = rng.random((batch_size, num_fields))
        raw = np.floor(np.clip(u ** (-1.0 / (alpha - 1.0)), 1.0, 2.0 ** 62)).astype(np.int64)
        ids64 = hash_category(raw.astype(np.uint64), fields, id_space)
        dense = rng.normal(size=(batch_size, dense_dim)).astype(np.float32)
        logit = dense @ w_dense + 0.01 * (ids64 % 97 - 48).sum(axis=1) / num_fields
        label = (rng.random(batch_size) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)
        out.append({"sparse": {feature: ids64.astype(np.int32)}, "dense": dense,
                    "label": label})
    return out


def stack(batches: List[Dict]) -> Dict:
    """K batches -> one pytree whose leaves have a leading K dim (the scan's feed)."""
    return {"sparse": {k: np.stack([b["sparse"][k] for b in batches])
                       for k in batches[0]["sparse"]},
            "dense": np.stack([b["dense"] for b in batches]),
            "label": np.stack([b["label"] for b in batches])}
