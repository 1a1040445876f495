"""Weights and tables from `--seed`, made by the benchmark and not by the program.

Every value is a pure function of (seed, stream, row, column): a 32-bit integer
hash turned into an Irwin-Hall(4) near-normal. So the full table is one jitted
elementwise call on the device (sharded where the table is), and the plain
reference evaluates the SAME function on the touched rows alone, with nothing
taken from the program. The seed enters as a traced uint32 key, so one compiled
program serves every seed.
"""

from __future__ import annotations

import zlib

import jax.numpy as jnp
import numpy as np

_M1, _M2 = 0x85EBCA6B, 0xC2B2AE35


def stream_key(seed: int, stream: str) -> np.uint32:
    """uint32 key of (seed, leaf name); seeds beyond 32 bits keep all their bits."""
    seed = int(seed)
    lo, hi = seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF
    x = (lo * 0x9E3779B1 + hi * 0x7FEB352D + zlib.crc32(stream.encode())) & 0xFFFFFFFF
    x ^= x >> 16
    x = (x * _M1) & 0xFFFFFFFF
    x ^= x >> 13
    x = (x * _M2) & 0xFFFFFFFF
    x ^= x >> 16
    return np.uint32(x)


def _mix(x):
    x = x ^ (x >> 16)
    x = x * jnp.uint32(_M1)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(_M2)
    return x ^ (x >> 16)


def unit_normal(key, rows, cols):
    """Near-normal (mean 0, variance 1) f32 of shape rows.shape + cols.shape's
    broadcast: `rows`, `cols` are integer arrays that broadcast together."""
    r = rows.astype(jnp.uint32)
    c = cols.astype(jnp.uint32)
    base = r * jnp.uint32(0x9E3779B1) + c * jnp.uint32(0x85EBCA77) + key.astype(jnp.uint32)
    h1 = _mix(base)
    h2 = _mix(base ^ jnp.uint32(0x68E31DA4))
    total = ((h1 & 0xFFFF) + (h1 >> 16) + (h2 & 0xFFFF) + (h2 >> 16)).astype(jnp.float32)
    # four uniforms on [0, 65535]: mean 2*65535, variance 4*(65536^2-1)/12
    return (total - 131070.0) * jnp.float32(1.0 / 37837.2266)


def table_rows(key, ids, width: int, stddev: float, zero_cols: int):
    """Rows `ids` (any shape) of a (V, width) table: N(0, stddev) but the first
    `zero_cols` columns, which start at 0 (a first-order weight)."""
    cols = jnp.arange(width, dtype=jnp.uint32)
    vals = unit_normal(key, ids[..., None], cols) * jnp.float32(stddev)
    return jnp.where(cols < zero_cols, jnp.float32(0.0), vals)


def dense_leaf(key, shape, stddev: float):
    """A dense-tower leaf: kernels N(0, stddev), flat index as the row."""
    n = int(np.prod(shape))
    flat = unit_normal(key, jnp.arange(n, dtype=jnp.uint32), jnp.uint32(0))
    return (flat * jnp.float32(stddev)).reshape(shape)
