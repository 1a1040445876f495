"""Quantized wire payloads for the sharded exchange (`parallel/sharded.py`).

The ICI wire protocol moves three payload classes per train step: id buckets
out, pulled rows back, pushed grads+counts out. Table STORAGE and the fused
optimizer apply stay fp32 (master weights) — only the bytes on the wire are
reduced, dequantized at the receiving edge. SparCML (arxiv 1802.08021) and
EQuARX (arxiv 2506.17615) both show sparse/quantized collectives recovering
2-4x wire bandwidth in exactly this regime.

Since round 13 the narrow payloads go THROUGH the collectives: rows are
encoded at the owner edge (before the pull all_to_all) and grads at the
client edge (before the push all_to_all), so the compiled a2a operands are
int8/bf16 — verified per config against the compiled HLO by the oelint
hlo-budget pass, not just by this module's analytic model.

Formats (`OETPU_WIRE`, default bf16; trainers can override explicitly):

- ``fp32``: payloads travel in their native float dtype (bit-exact; the
  pre-round-6 protocol). The test suite pins this via `tests/conftest.py` so
  mesh-vs-single-device parity stays exact; wire-specific tests opt in to the
  lossy formats explicitly.
- ``bf16``: rows and grads truncate to bfloat16 on the wire (2x fewer payload
  bytes vs fp32; ~3 decimal digits, plenty for embedding pulls and grads).
- ``int8``: rows and grads quantize to int8 with one fp32 scale per
  `INBAND_BLOCK`-wide block of the row (max-abs / 127), the scales riding
  IN-BAND as 4 bitcast int8 lanes per block beside the payload in the same
  a2a buffer (~4x fewer payload bytes; opt-in). All shapes are static in
  (dim, fmt), so switching nothing re-jits. For dim <= INBAND_BLOCK this
  degenerates to the round-6 single per-row scale bit-for-bit.

Duplicate COUNTS (the push's second payload) must survive the wire EXACTLY —
they divide/weight optimizer updates — so they always ride as raw int32 bits
BITCAST into wire lanes (1 fp32 lane, 2 bf16 lanes, or 4 int8 lanes), never
quantized. Empty bucket slots are zero-filled: zero bits decode to grad 0,
scale 0, count 0 in every format, so no validity mask rides the wire.

Stochastic rounding (``pack_inband(..., stochastic=True)``): int8 grad
pushes round with a deterministic hash dither derived from the value bits
and lane position (key-free, replica-reproducible) instead of
round-to-nearest, removing the systematic rounding bias that would otherwise
accumulate over training steps. Row pulls keep round-to-nearest (their bias
is handled by the pull-side error-feedback residuals, `EmbeddingTableState.ef`).
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import trace as _trace

WIRE_ENV = "OETPU_WIRE"
DEFAULT_WIRE = "bf16"
FORMATS = ("fp32", "bf16", "int8")
_ALIASES = {"float32": "fp32", "f32": "fp32", "bfloat16": "bf16",
            "i8": "int8"}

# int8 payloads carry one fp32 scale per block as 4 bitcast int8 lanes
_SCALE_LANES = 4
# columns sharing one in-band scale; dim <= INBAND_BLOCK keeps the round-6
# one-scale-per-row layout (and its wire width) exactly
INBAND_BLOCK = 32


def wire_format(override: Optional[str] = None) -> str:
    """Resolve the wire format: explicit override > $OETPU_WIRE > bf16."""
    fmt = override or os.environ.get(WIRE_ENV, "") or DEFAULT_WIRE
    fmt = _ALIASES.get(fmt.lower(), fmt.lower())
    if fmt not in FORMATS:
        raise ValueError(
            f"unknown wire format {fmt!r} (expected one of {FORMATS}; "
            f"set {WIRE_ENV} or the trainer's wire= argument)")
    return fmt


def wire_dtype(fmt: str):
    """The VALUE dtype payloads are encoded in (fp32 keeps the native
    float). Sizing authority for every cost model — itemsize 4/2/1."""
    return {"fp32": jnp.float32, "bf16": jnp.bfloat16,
            "int8": jnp.int8}[fmt]


def wire_carrier_dtype(fmt: str):
    """The array dtype the a2a BUFFERS actually travel in. bf16 ships its
    bit pattern as uint16: XLA:CPU's float-normalization pass legalizes
    bf16 ops — collectives included — to f32 with converts, which would
    silently double the compiled payload on the backend the hlo-budget
    world measures; an integer carrier is 2 bytes/lane on every backend
    (and matches the numpy codec, which represents bf16 as uint16)."""
    return {"fp32": jnp.float32, "bf16": jnp.uint16,
            "int8": jnp.int8}[fmt]


def count_lanes(fmt: str) -> int:
    """Lanes one bitcast int32 count occupies in the wire dtype."""
    return 4 // jnp.dtype(wire_dtype(fmt)).itemsize


def scale_blocks(dim: int) -> int:
    """In-band fp32 scales an int8-encoded (n, dim) payload carries per row."""
    return -(-dim // INBAND_BLOCK)


# ---------------------------------------------------------------------------
# Exact int32 <-> wire-lane bitcasts (duplicate counts).
# ---------------------------------------------------------------------------


def counts_to_lanes(counts: jax.Array, fmt: str) -> jax.Array:
    """(n,) int32 -> (n, count_lanes(fmt)) in the wire CARRIER dtype,
    bit-exact."""
    lanes = jax.lax.bitcast_convert_type(counts.astype(jnp.int32),
                                         wire_carrier_dtype(fmt))
    return lanes.reshape(counts.shape[0], -1)


def lanes_to_counts(lanes: jax.Array) -> jax.Array:
    """Inverse of counts_to_lanes: (n, L) wire lanes -> (n,) int32."""
    if lanes.shape[1] == 1:
        return jax.lax.bitcast_convert_type(lanes[:, 0], jnp.int32)
    return jax.lax.bitcast_convert_type(lanes, jnp.int32).reshape(-1)


# ---------------------------------------------------------------------------
# Row payloads (the pull's second all_to_all).
# ---------------------------------------------------------------------------


def rows_wire_width(dim: int, fmt: str) -> int:
    """Wire columns for a (n, dim) float row payload (int8: + the in-band
    scale lanes, 4 per INBAND_BLOCK-wide block)."""
    return dim + _SCALE_LANES * scale_blocks(dim) if fmt == "int8" else dim


def _dither(x32: jax.Array) -> jax.Array:
    """Deterministic stochastic-rounding dither in [0, 1): a key-free hash of
    the value bits xor'd with the lane position (so equal values in different
    lanes dither differently), mixed with two xorshift-multiply rounds. Pure
    function of the input — identical on every replica, never a PRNG key to
    thread through the exchange."""
    bits = jax.lax.bitcast_convert_type(x32.astype(jnp.float32), jnp.uint32)
    lane = (jnp.arange(x32.shape[-1], dtype=jnp.uint32)
            * jnp.uint32(2654435761))
    h = bits ^ lane
    h = (h ^ (h >> 16)) * jnp.uint32(0x7FEB352D)
    h = (h ^ (h >> 15)) * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return (h >> 8).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _quantize_int8(x32: jax.Array, stochastic: bool = False) -> jax.Array:
    """(n, d) f32 -> (n, rows_wire_width(d, 'int8')) int8: symmetric max-abs
    scaling per INBAND_BLOCK-wide block, the fp32 scales bitcast into the
    trailing 4*blocks lanes (in-band — the scales ride the same a2a buffer).
    All-zero blocks get scale 0 and decode to exact zeros."""
    n, dim = x32.shape
    nb = scale_blocks(dim)
    pad = nb * INBAND_BLOCK - dim
    xb = jnp.pad(x32, ((0, 0), (0, pad))) if pad else x32
    xb = xb.reshape(n, nb, INBAND_BLOCK)
    amax = jnp.max(jnp.abs(xb), axis=2)
    scale = amax / 127.0
    inv = jnp.where(scale > 0, 1.0 / scale, 0.0)
    scaled = xb * inv[:, :, None]
    if stochastic:
        qf = jnp.floor(scaled + _dither(xb))
    else:
        qf = jnp.round(scaled)
    q = jnp.clip(qf, -127, 127).astype(jnp.int8)
    q = q.reshape(n, nb * INBAND_BLOCK)[:, :dim]
    scale_lanes = jax.lax.bitcast_convert_type(
        scale.astype(jnp.float32), jnp.int8).reshape(n, nb * _SCALE_LANES)
    return jnp.concatenate([q, scale_lanes], axis=1)


def _dequantize_int8(wire: jax.Array, dim: int) -> jax.Array:
    """(n, rows_wire_width(dim, 'int8')) int8 -> (n, dim) f32."""
    n = wire.shape[0]
    nb = scale_blocks(dim)
    scale = jax.lax.bitcast_convert_type(
        wire[:, dim:dim + _SCALE_LANES * nb].reshape(n, nb, _SCALE_LANES),
        jnp.float32)
    pad = nb * INBAND_BLOCK - dim
    q = wire[:, :dim].astype(jnp.float32)
    qb = jnp.pad(q, ((0, 0), (0, pad))) if pad else q
    out = qb.reshape(n, nb, INBAND_BLOCK) * scale[:, :, None]
    return out.reshape(n, nb * INBAND_BLOCK)[:, :dim]


def pack_inband(rows: jax.Array, fmt: str, *,
                stochastic: bool = False) -> jax.Array:
    """(n, d) float rows -> wire payload (n, rows_wire_width(d, fmt)) with
    any scales packed in-band. Static shapes in (d, fmt): switching the wire
    format never re-jits a fixed-format program. `stochastic` selects
    hash-dithered stochastic rounding (int8 only; fp32/bf16 ignore it)."""
    with _trace.scope("exchange", "wire"):
        if fmt == "fp32":
            return rows
        if fmt == "bf16":
            # uint16 carrier — see wire_carrier_dtype for why not bf16 itself
            return jax.lax.bitcast_convert_type(
                rows.astype(jnp.bfloat16), jnp.uint16)
        return _quantize_int8(rows.astype(jnp.float32), stochastic=stochastic)


def unpack_inband(wire: jax.Array, dim: int, fmt: str) -> jax.Array:
    """Inverse of pack_inband -> (n, d) float32 (callers cast to their
    compute/table dtype — exact for bf16-kept tables)."""
    with _trace.scope("exchange", "wire"):
        if fmt == "int8":
            return _dequantize_int8(wire, dim)
        if fmt == "bf16":
            return jax.lax.bitcast_convert_type(
                wire, jnp.bfloat16).astype(jnp.float32)
        return wire.astype(jnp.float32)


def encode_rows(rows: jax.Array, fmt: str) -> jax.Array:
    """(n, d) float rows -> wire payload (round-to-nearest alias of
    pack_inband, kept as the stable codec entry point)."""
    return pack_inband(rows, fmt)


def decode_rows(wire: jax.Array, dim: int, fmt: str) -> jax.Array:
    """Inverse of encode_rows -> (n, d) float32."""
    return unpack_inband(wire, dim, fmt)


# ---------------------------------------------------------------------------
# Host-side row codecs (numpy) — the online-sync wire (`sync/`).
#
# The model-sync feed ships delta rows trainer -> serving replica over HTTP;
# neither edge wants a device round-trip just to (de)quantize, so the same
# three formats get a pure-numpy implementation. Semantics match the jnp
# codecs above: bf16 truncates with round-to-nearest-even (what
# `astype(bfloat16)` does in XLA), int8 is symmetric per-block max-abs with
# the fp32 scales riding as 4 bitcast lanes per block. bf16 payloads are
# REPRESENTED as uint16 (numpy has no native bfloat16); `fmt` travels beside
# the payload.
# ---------------------------------------------------------------------------


def np_wire_dtype(fmt: str):
    """The numpy dtype an encoded row payload is stored/shipped as."""
    return {"fp32": np.float32, "bf16": np.uint16, "int8": np.int8}[fmt]


def np_encode_rows(rows: np.ndarray, fmt: str) -> np.ndarray:
    """(n, d) float rows -> host wire payload (n, rows_wire_width(d, fmt))."""
    rows = np.ascontiguousarray(rows, np.float32)
    if fmt == "fp32":
        return rows
    if fmt == "bf16":
        u = rows.view(np.uint32)
        # round-to-nearest-even truncation to the high 16 bits
        bias = np.uint32(0x7FFF) + ((u >> np.uint32(16)) & np.uint32(1))
        return ((u + bias) >> np.uint32(16)).astype(np.uint16)
    n, dim = rows.shape
    nb = scale_blocks(dim)
    pad = nb * INBAND_BLOCK - dim
    xb = (np.pad(rows, ((0, 0), (0, pad))) if pad else rows) \
        .reshape(n, nb, INBAND_BLOCK)
    amax = np.max(np.abs(xb), axis=2)
    scale = (amax / 127.0).astype(np.float32)
    inv = np.zeros_like(scale)
    np.divide(np.float32(1.0), scale, out=inv, where=scale > 0)
    q = np.clip(np.rint(xb * inv[:, :, None]), -127, 127).astype(np.int8)
    q = q.reshape(n, nb * INBAND_BLOCK)[:, :dim]
    scale_lanes = np.ascontiguousarray(scale).view(np.int8) \
        .reshape(n, nb * _SCALE_LANES)
    return np.concatenate([q, scale_lanes], axis=1)


def np_decode_rows(wire: np.ndarray, dim: int, fmt: str) -> np.ndarray:
    """Inverse of np_encode_rows -> (n, dim) float32."""
    if fmt == "fp32":
        return np.asarray(wire, np.float32)
    if fmt == "bf16":
        u16 = np.ascontiguousarray(wire, dtype=np.uint16)
        return (u16.astype(np.uint32) << np.uint32(16)).view(np.float32)
    w = np.ascontiguousarray(wire, dtype=np.int8)
    n = w.shape[0]
    nb = scale_blocks(dim)
    scale = np.ascontiguousarray(
        w[:, dim:dim + _SCALE_LANES * nb]).view(np.float32) \
        .reshape(n, nb)
    pad = nb * INBAND_BLOCK - dim
    q = w[:, :dim].astype(np.float32)
    qb = np.pad(q, ((0, 0), (0, pad))) if pad else q
    out = qb.reshape(n, nb, INBAND_BLOCK) * scale[:, :, None]
    return np.ascontiguousarray(out.reshape(n, nb * INBAND_BLOCK)[:, :dim])


def sync_delta_cost(tables: Dict[str, Tuple[int, int]], fmt: str) -> dict:
    """Static wire cost of shipping ONE committed delta to a serving replica
    (`sync/publisher.py` serves it, `utils/metrics.observe_sync_cost` gauges
    it): per table {name: (touched_rows, dim)}, ids travel as exact int64
    (8 B/row — never quantized, like the exchange's id lanes) and rows as the
    chosen wire format, in-band scale lanes included (`bytes_scales` breaks
    them out). Optimizer slots never ride this wire at all — the serving feed
    is weights-only, so even fp32 sync ships ~half the bytes the delta holds
    on disk."""
    bytes_ids = bytes_rows = bytes_scales = rows_total = 0
    w = np.dtype(np_wire_dtype(fmt)).itemsize
    for _name, (n, dim) in tables.items():
        bytes_ids += n * 8
        bytes_rows += n * rows_wire_width(dim, fmt) * w
        if fmt == "int8":
            bytes_scales += n * _SCALE_LANES * scale_blocks(dim) * w
        rows_total += n
    return {"format": fmt, "rows": int(rows_total),
            "wire_dtype": str(np.dtype(np_wire_dtype(fmt))),
            "bytes_ids": int(bytes_ids), "bytes_rows": int(bytes_rows),
            "bytes_scales": int(bytes_scales),
            "bytes_total": int(bytes_ids + bytes_rows)}


# ---------------------------------------------------------------------------
# Grad+count payloads (the push's single all_to_all).
# ---------------------------------------------------------------------------


def grads_wire_width(dim: int, fmt: str) -> int:
    """Wire columns for a (n, dim) grad payload + its exact count lanes."""
    return rows_wire_width(dim, fmt) + count_lanes(fmt)


def encode_grads(grads: jax.Array, counts: jax.Array, fmt: str, *,
                 stochastic: bool = False) -> jax.Array:
    """(n, d) float grads + (n,) int32 counts -> (n, grads_wire_width) wire
    rows. Counts ride bit-exact; grads quantize like rows (`stochastic`
    selects the int8 hash-dither rounding the training push uses)."""
    with _trace.scope("exchange", "wire"):
        g = pack_inband(grads.astype(jnp.float32) if fmt != "bf16" else grads,
                        fmt, stochastic=stochastic)
        return jnp.concatenate([g, counts_to_lanes(counts, fmt)], axis=1)


def decode_grads(wire: jax.Array, dim: int, fmt: str):
    """-> ((n, d) float32 grads, (n,) int32 counts)."""
    with _trace.scope("exchange", "wire"):
        body = rows_wire_width(dim, fmt)
        return unpack_inband(wire[:, :body], dim, fmt), lanes_to_counts(
            wire[:, body:])


# ---------------------------------------------------------------------------
# Sparse top-k payloads (the dense ZeRO grad exchange, dense_wire=
# "sparse_topk"). SparCML (arxiv 1802.08021) stream-sparse collectives with
# the house in-band layout: k is a TRACE-TIME constant, so the payload shape
# is static and the sparse mode compiles to ordinary fixed-shape a2as — no
# host round-trip, no dynamic shapes. Each payload row carries the k
# largest-|x| elements of a dense vector as int8 value lanes (per-
# INBAND_BLOCK fp32 scales in-band, same codec as rows) followed by their
# int32 column indices bitcast into 4 trailing int8 lanes per value.
# Untransmitted elements decode to EXACT zeros, so the decode-side residual
# (x - unpack_topk(pack_topk(x))) is precisely the untransmitted mass the
# `__dense_ef__` error-feedback slots accumulate.
# ---------------------------------------------------------------------------

# bitcast int8 lanes per transmitted element's int32 column index
_INDEX_LANES = 4


def topk_wire_width(k: int) -> int:
    """Wire columns of one sparse top-k payload row in the int8 carrier:
    k quantized value lanes + their in-band scale lanes + 4 bitcast index
    lanes per value. Bytes/element ~= 1 + 4 + 4/INBAND_BLOCK ~= 5.125 —
    the honest sparse price `zero.dense_wire_cost` and the Densifying
    (arxiv 1905.04035) crossover rule in `PlacementPolicy` both use."""
    return rows_wire_width(k, "int8") + _INDEX_LANES * k


def pack_topk(x: jax.Array, k: int) -> jax.Array:
    """(n, m) f32 -> (n, topk_wire_width(k)) int8: per row the k largest-
    magnitude elements, int8-quantized with per-INBAND_BLOCK in-band fp32
    scales (partial trailing blocks pad exactly like the row codec), plus
    their int32 column indices bitcast into trailing lanes. k must be a
    static 1 <= k <= m; top_k index sets are distinct per row, so the
    decode scatter is collision-free by construction."""
    n, m = x.shape
    if not 1 <= k <= m:
        raise ValueError(f"pack_topk: k={k} outside [1, {m}]")
    idx = jax.lax.top_k(jnp.abs(x), k)[1]  # (n, k) int32, indices distinct
    vals = jnp.take_along_axis(x, idx, axis=1)
    q = _quantize_int8(vals.astype(jnp.float32))
    lanes = jax.lax.bitcast_convert_type(
        idx.astype(jnp.int32), jnp.int8).reshape(n, _INDEX_LANES * k)
    return jnp.concatenate([q, lanes], axis=1)


def unpack_topk(wire: jax.Array, k: int, m: int) -> jax.Array:
    """Inverse of pack_topk -> dense (n, m) f32 with every untransmitted
    element exactly 0."""
    n = wire.shape[0]
    body = rows_wire_width(k, "int8")
    vals = _dequantize_int8(wire[:, :body], k)
    idx = jax.lax.bitcast_convert_type(
        wire[:, body:].reshape(n, k, _INDEX_LANES), jnp.int32)
    out = jnp.zeros((n, m), jnp.float32)
    return out.at[jnp.arange(n)[:, None], idx].set(vals)


# ---------------------------------------------------------------------------
# Static wire-cost model (bytes/step, collectives/step) — what the metrics
# gauges and PERF.md report. The model prices the a2a RESULT buffers (S * cap
# slots per table, self-shard included), which is exactly what the oelint
# hlo-budget pass counts out of the compiled HLO — `wire_model_delta` in
# tools/oelint/hlo_budget.json pins model == HLO.
# ---------------------------------------------------------------------------


def id_wire_itemsize(pair: bool, itemsize: int) -> int:
    """Bytes per bucket slot in the fused id exchange: pair layout = 8
    (2 uint32 lanes), single-lane = the native int itemsize."""
    return 8 if pair else itemsize


def exchange_cost(tables, num_shards: int, fmt: str) -> dict:
    """Static per-device wire cost of one train step.

    `tables`: list of dicts {dim, cap, pair (bool), id_itemsize} — one per
    PS table, `cap` the per-(src,dst) bucket capacity of ITS batch. Each
    table may carry an optional `fmt` overriding the call-level format (the
    per-table wire dict, round 17); tables sharing (dim, fmt) form one
    dim-group — a mixed-format dim splits into one fused group per format,
    exactly how `MeshTrainer._exchange_groups` splits the compiled a2as.
    Bytes are what ONE device ships through the three all_to_alls (recv
    volume is symmetric). `bytes_scales` breaks out the in-band scale lanes
    (int8 only) already included in the row/grad totals — the honest price
    of the in-collective format.
    """
    S = num_shards
    groups = {}
    for t in tables:
        groups.setdefault((t["dim"], t.get("fmt", fmt)), []).append(t)
    w = jnp.dtype(wire_dtype(fmt)).itemsize
    bytes_ids = bytes_rows = bytes_grads = bytes_scales = 0
    for (dim, tf), members in groups.items():
        # a group widens mixed-layout ids to the common wire layout; a
        # uniform group keeps its native layout (see dedup.concat_owner_buckets)
        pair_wire = any(m["pair"] for m in members)
        iid = max(m["id_itemsize"] for m in members)
        tw = jnp.dtype(wire_dtype(tf)).itemsize
        for m in members:
            cap = m["cap"]
            bytes_ids += S * cap * id_wire_itemsize(pair_wire, iid)
            bytes_rows += S * cap * rows_wire_width(dim, tf) * tw
            bytes_grads += S * cap * grads_wire_width(dim, tf) * tw
            if tf == "int8":
                # one set of scale lanes in the row payload, one in the grads
                bytes_scales += S * cap * _SCALE_LANES * scale_blocks(dim) \
                    * tw * 2
    total = bytes_ids + bytes_rows + bytes_grads
    return {"format": fmt, "num_shards": S,
            "dim_groups": len(groups), "tables": len(tables),
            "wire_dtype": str(jnp.dtype(wire_dtype(fmt))),
            "wire_itemsize": int(w),
            "collectives_per_step": 3 * len(groups) if S > 1 else 0,
            "bytes_ids": int(bytes_ids), "bytes_rows": int(bytes_rows),
            "bytes_grads": int(bytes_grads),
            "bytes_scales": int(bytes_scales) if S > 1 else 0,
            "bytes_per_step": int(total) if S > 1 else 0}


def conflict_patch_cost(tables, num_shards: int, fmt: str) -> dict:
    """Static per-device wire cost of the pipelined loop's conflict patch
    (`parallel/sharded.grouped_conflict_patch`): ONE extra all_to_all per
    (dim, fmt) group shipping `pcap` row+slot entries per (src, dst) pair,
    encoded with the push codec (row payload + exact count lanes carrying
    slot+1). `tables`: list of dicts {dim, cap, pcap} with the optional
    per-table `fmt` override, mirroring `exchange_cost`'s input — `pcap`
    from `parallel/sharded.conflict_patch_cap` (== cap in the exact default,
    bounded by conflict_factor otherwise). These are the ONLY wire bytes
    pipelining adds on top of the serial exchange; everything else just
    moves off the critical path ("overlapped_bytes")."""
    S = num_shards
    groups = {}
    for t in tables:
        groups.setdefault((t["dim"], t.get("fmt", fmt)), []).append(t)
    bytes_patch = 0
    for (dim, tf), members in groups.items():
        tw = jnp.dtype(wire_dtype(tf)).itemsize
        for m in members:
            bytes_patch += S * m["pcap"] * grads_wire_width(dim, tf) * tw
    return {"format": fmt, "num_shards": S,
            "collectives": len(groups) if S > 1 else 0,
            "bytes_patch": int(bytes_patch) if S > 1 else 0}


def hot_reduce_cost(hot_rows_by_table, num_shards: int, fmt: str) -> dict:
    """Static per-device cost model of the hot-row gradient reduction
    (`parallel/sharded._hot_apply`), per hot format:

    - fp32 / bf16: one ring all-reduce of the dense (H, dim) aggregate,
      ~2*(S-1)/S * H * dim * itemsize bytes per device;
    - int8: the two-stage quantized reduce — an all_to_all of the encoded
      (Hp, W) buffer plus an all_gather of the re-encoded partial sums, each
      a full Hp * W int8 result buffer (Hp = H padded to a multiple of S,
      W = rows_wire_width(dim, 'int8')) — `a2a_bytes` / `all_gather_bytes`
      are what the hlo-budget counter sees for those collectives.

    `hot_rows_by_table`: list of dicts {dim, hot} (hot = H, rows cached).
    The exact int32 count psum (H * 4 bytes) rides in `bytes` for every
    format.
    """
    S = num_shards
    ring = 2 * (S - 1) / S if S > 1 else 0
    total = a2a = ag = 0
    for t in hot_rows_by_table:
        H, dim = t["hot"], t["dim"]
        if H <= 0 or S <= 1:
            continue
        total += int(ring * H * 4)  # exact int32 counts psum
        if fmt == "int8":
            Hp = -(-H // S) * S
            W = rows_wire_width(dim, "int8")
            a2a += Hp * W
            ag += Hp * W
            total += 2 * Hp * W
        else:
            w = jnp.dtype(wire_dtype(fmt)).itemsize
            total += int(ring * H * dim * w)
    return {"format": fmt, "bytes": int(total),
            "a2a_bytes": int(a2a), "all_gather_bytes": int(ag)}
