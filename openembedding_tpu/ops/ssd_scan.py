"""The Mamba-2 chunked scan (`models/nemotron_h.ssd_chunked`) as ONE TPU
kernel a direction: a chunk's (Q x Q) decays and the state carried between
chunks live in VMEM only, and every operand keeps the model's own layout (a
position's heads side by side), so XLA moves nothing around the kernel.

`scan(x, dt, acs, b, c, groups, docs=None)`: x (Bt, nc, Q, H * P); dt (Bt,
nc, H, Q) f32 and acs, each head's decays dt A cumulated inside a chunk, the
same; b, c (Bt, nc, Q, G * N) in the products' dtype; `docs` (Bt, nc, Q)
int32, the document of each position (`nemotron_h.segment_ids`), or None.
-> y (Bt, nc, Q, H * P) f32. With xd = dt x (made in the kernel, in f32), for
chunk z, head h (of group g), positions l, s of the chunk:

    y[l]  = sum_{s <= l} (C_l . B_s) exp(acs[l] - acs[s]) xd[s]    (in chunk)
          + reach[l] C_l . S_z                                      (read)
    S_z+1 = keep_z exp(acs[Q-1]) S_z + sum_s B_s (x) xd[s] left[s]  (state)

with reach = exp(acs), left = exp(acs[Q-1] - acs) and S_0 = 0. With
documents a pair (l, s) counts where both are in one document (a mask of
PAIRS), reach where l is in the document the state entering the chunk
belongs to, left where s is in the chunk's last document, and keep_z where no
document starts in chunk z (the plain body's `carry` mask, as a recurrence).

The grid is (batch, group, block of `HEADS` heads of the group, chunk); the
chunk runs in order (backward in reverse) with the state of the block's heads
(N, HEADS * P) in a VMEM scratch. A lane tile of 128 holds 128 / P heads; a
head's operand in the in-chunk product is its tile with the other heads'
lanes zeroed, so every product and every store is a whole lane tile. The
pairs are (128 x 128) tiles; the tiles above the diagonal, which no causal
pair reaches, are skipped.

- forward (`_fwd_kernel`): C . B^T, then for each head and tile the pair
  differences, the mask, their exponent, the product with C . B^T in f32,
  rounded to the operand dtype and multiplied by xd; the read of the entering
  state; the state's update. It also writes the state entering each chunk
  (the backward's residual).
- backward (`_bwd_kernel`): the decays made again, never read from HBM; the
  gradient of the state leaving the chunk carried from the later chunks;
  d x and d dt through xd, d acs (row sums minus column sums of dM * cb *
  decay, and the reach, left and carry terms), and d b, d c of the block's
  heads (summed over the group's blocks by XLA).

Precision: decays, masks, exponents and every accumulation are f32; every
product takes operands in b's dtype (xd, xd * left, the state, C . B^T *
decay rounded there), as `nemotron_h._mm` does. The state is carried in f32:
the plain body rounds the chunk states and the carry to the products' dtype
before summing them, so the two agree to that rounding (in f32 to summation
order and the rounding of exp(a) exp(b) against exp(a + b)).

Which shapes the kernel takes is `takes`'s rule, from the shapes alone.
`interpret=True` runs the Pallas interpreter (CPU tests; the program never
passes it).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEADS = 8                           # heads of one group a grid step
T = 128                             # the side of a tile of pairs; a lane tile
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_F32 = jnp.float32


def takes(Q: int, H: int, G: int, P: int, N: int) -> bool:
    """Whether the kernel takes a call at these shapes: the chunk Q a
    multiple of 128 up to 512 (whole tiles of pairs), the heads of a group a
    multiple of `HEADS`, the head width P 64 or 128 (whole heads in a lane
    tile), the state N 128 (one lane tile of B and C)."""
    return not (H % G or (H // G) % HEADS or Q % T or Q > 512
                or P not in (64, 128) or N != T)


def _tiles(Q):
    """The (row, column) tiles of a chunk's pairs on or below the diagonal."""
    return [(i, j) for i in range(Q // T) for j in range(i + 1)]


def _lanes(P):
    """For each head of a lane tile, the (1, T) lanes that are its own (None
    where it fills the tile)."""
    if P == T:
        return [None]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    return [(lane >= u * P) & (lane < (u + 1) * P) for u in range(T // P)]


def _own(x, lanes):
    return x if lanes is None else jnp.where(lanes, x, jnp.zeros_like(x))


def _by_lane(values, lanes):
    """One head's value on each head's lanes of a lane tile."""
    out = values[-1]
    for v, own in zip(values[:-1], lanes[:-1]):
        out = jnp.where(own, v, out)
    return out


class _Chunk:
    """What a grid step reads of its chunk: the pair masks, the head block's
    cumulated decays (rows, and columns broadcast over a lane tile), and the
    per-lane reach, left and carry factors of each lane tile."""

    def __init__(self, dt_ref, acs_ref, docs_refs, P):
        self.lanes = _lanes(P)
        self.per = len(self.lanes)
        self.dt = dt_ref[...]                       # (HEADS, Q)
        acs = acs_ref[...]                          # (HEADS, Q)
        Q = acs.shape[1]
        self.Q = Q
        self.acs = acs
        self.end = acs[:, Q - 1:Q]                  # (HEADS, 1)
        l = jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
        s = jax.lax.broadcasted_iota(jnp.int32, (T, T), 1)
        self.masks = {}
        self.reach_ok = self.left_ok = self.keep = None
        if docs_refs:
            row_ref, col_ref, nin_ref = docs_refs
            col = col_ref[...]                      # (Q, 1)
            n_end = row_ref[:, Q - 1:Q]             # (1, 1)
            n_in = nin_ref[:, 0:1]
            self.reach_ok = col == n_in
            self.left_ok = col == n_end
            self.keep = (n_end == n_in).astype(_F32)
        for i, j in _tiles(Q):
            seen = (s <= l) if i == j else None
            if docs_refs:
                same = (col_ref[pl.ds(i * T, T), :]
                        == row_ref[:, pl.ds(j * T, T)])
                seen = same if seen is None else seen & same
            self.masks[i, j] = seen

    def column(self, h):
        """Head h's cumulated decays down the chunk, on every lane (a row
        broadcast over a lane tile's sublanes and turned: the transpose
        unit makes the lanes, cheaper than broadcasting a column)."""
        return jnp.broadcast_to(self.acs[h:h + 1], (T, self.Q)).T

    def steps(self, t):
        """Lane tile t's dt, each head's on its lanes (Q, T)."""
        return _by_lane([jnp.broadcast_to(self.dt[h:h + 1], (T, self.Q)).T
                         for h in range(t * self.per, (t + 1) * self.per)],
                        self.lanes)

    def decay(self, column, h, i, j):
        """Head h's decays exp(acs[l] - acs[s]) over tile (i, j), 0 where the
        pair does not count (its exponent is never taken)."""
        seg = column[i * T:(i + 1) * T] - self.acs[h:h + 1, j * T:(j + 1) * T]
        seen = self.masks[i, j]
        if seen is not None:
            seg = jnp.where(seen, seg, -jnp.inf)
        return jnp.exp(seg)

    def factors(self, t, columns):
        """Lane tile t's reach (Q, T), left (Q, T) and carry factor (1, T)."""
        heads = [t * self.per + u for u in range(self.per)]
        column = _by_lane(columns, self.lanes)
        end = _by_lane([self.end[h:h + 1] for h in heads], self.lanes)
        reach, left, carry = jnp.exp(column), jnp.exp(end - column), \
            jnp.exp(end)
        if self.reach_ok is not None:
            reach = jnp.where(self.reach_ok, reach, 0.0)
            left = jnp.where(self.left_ok, left, 0.0)
            carry = carry * self.keep
        return reach, left, carry


def _fwd_kernel(x_ref, dt_ref, acs_ref, b_ref, c_ref, *refs, P, docs):
    y_ref, sent_ref, s_ref = refs[-3:]
    ch = _Chunk(dt_ref, acs_ref, refs[:3] if docs else (), P)
    Q = ch.Q

    @pl.when(pl.program_id(3) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    sent_ref[...] = s_ref[...]
    bm, cm = b_ref[...], c_ref[...]
    dtype = bm.dtype
    cb = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=_F32)
    for t, lane_tile in enumerate(range(0, x_ref.shape[-1], T)):
        tile = pl.ds(lane_tile, T)
        xt = x_ref[:, tile].astype(_F32) * ch.steps(t)     # xd = dt x
        xb = xt.astype(dtype)
        acc = [None] * (Q // T)
        columns = []
        for u, lanes in enumerate(ch.lanes):
            h = t * ch.per + u
            x = _own(xb, lanes)
            column = ch.column(h)
            columns.append(column)
            for i, j in _tiles(Q):
                m = (cb[i * T:(i + 1) * T, j * T:(j + 1) * T]
                     * ch.decay(column, h, i, j)).astype(dtype)
                part = jnp.dot(m, x[j * T:(j + 1) * T],
                               preferred_element_type=_F32)
                acc[i] = part if acc[i] is None else acc[i] + part
        reach, left, carry = ch.factors(t, columns)
        state = s_ref[:, tile]
        read = jnp.dot(cm, state.astype(dtype), preferred_element_type=_F32)
        y_ref[:, tile] = jnp.concatenate(acc, axis=0) + reach * read
        fed = jax.lax.dot_general(bm, (xt * left).astype(dtype), _TN,
                                  preferred_element_type=_F32)
        s_ref[:, tile] = carry * state + fed


def _bwd_kernel(x_ref, dt_ref, acs_ref, b_ref, c_ref, sent_ref, dy_ref, *refs,
                P, docs):
    dx_ref, ddt_ref, dacs_ref, db_ref, dc_ref, ds_ref, dcb_ref = refs[-7:]
    ch = _Chunk(dt_ref, acs_ref, refs[:3] if docs else (), P)
    Q = ch.Q
    heads = x_ref.shape[-1] // P

    @pl.when(pl.program_id(3) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    dcb_ref[...] = jnp.zeros_like(dcb_ref)
    bm, cm = b_ref[...], c_ref[...]
    dtype = bm.dtype
    cb = jax.lax.dot_general(cm, bm, _NT, preferred_element_type=_F32)
    head = jax.lax.broadcasted_iota(jnp.int32, (1, heads), 1)
    row_sums = jnp.zeros((Q, heads), _F32)
    dt_sums = jnp.zeros((Q, heads), _F32)
    ends = jnp.zeros((1, heads), _F32)
    db = dc = None
    for t, lane_tile in enumerate(range(0, x_ref.shape[-1], T)):
        tile = pl.ds(lane_tile, T)
        x32, steps = x_ref[:, tile].astype(_F32), ch.steps(t)
        xt, dyt = x32 * steps, dy_ref[:, tile]
        xb, dyb = xt.astype(dtype), dyt.astype(dtype)
        state, dnext = sent_ref[:, tile], ds_ref[:, tile]
        sb, dnb = state.astype(dtype), dnext.astype(dtype)
        columns = [ch.column(t * ch.per + u) for u in range(ch.per)]
        reach, left, carry = ch.factors(t, columns)
        # the read of the entering state
        read = jnp.dot(cm, sb, preferred_element_type=_F32)
        u = (dyt * reach).astype(dtype)
        ds_read = jax.lax.dot_general(cm, u, _TN, preferred_element_type=_F32)
        part = jax.lax.dot_general(u, sb, _NT, preferred_element_type=_F32)
        dc = part if dc is None else dc + part
        # the state this chunk feeds: d (xd * left) = B dS
        xl = xt * left
        v = jnp.dot(bm, dnb, preferred_element_type=_F32)
        part = jax.lax.dot_general(xl.astype(dtype), dnb, _NT,
                                   preferred_element_type=_F32)
        db = part if db is None else db + part
        fed = v * xl
        by_lane = dyt * reach * read - fed       # d acs of reach and left
        carried = jnp.sum(carry * state * dnext, axis=0, keepdims=True)
        ds_ref[:, tile] = carry * dnext + ds_read
        dx = jnp.split(v * left, Q // T, axis=0)
        for w, lanes in enumerate(ch.lanes):
            h = t * ch.per + w
            x, dy = _own(xb, lanes), _own(dyb, lanes)
            own = _own(by_lane, lanes)
            by_row = [own[i * T:(i + 1) * T] for i in range(Q // T)]
            col_sums = [None] * (Q // T)
            for i, j in _tiles(Q):
                decay = ch.decay(columns[w], h, i, j)
                cbt = cb[i * T:(i + 1) * T, j * T:(j + 1) * T]
                m = (cbt * decay).astype(dtype)
                dyi = dy[i * T:(i + 1) * T]
                part = jax.lax.dot_general(m, dyi, _TN,
                                           preferred_element_type=_F32)
                dx[j] = dx[j] + part
                dm = jax.lax.dot_general(dyi, x[j * T:(j + 1) * T], _NT,
                                         preferred_element_type=_F32)
                dm_decay = dm * decay
                dcb_ref[pl.ds(i * T, T), pl.ds(j * T, T)] += dm_decay
                dseg = dm_decay * cbt
                s = jnp.sum(dseg, axis=0, keepdims=True)
                by_row[i] = by_row[i] + dseg
                col_sums[j] = s if col_sums[j] is None else col_sums[j] + s
            dacs_ref[pl.ds(h, 1), :] = -jnp.concatenate(col_sums, axis=1)
            r = jnp.concatenate([jnp.sum(d, axis=1, keepdims=True)
                                 for d in by_row], axis=0)
            row_sums = jnp.where(head == h, r, row_sums)
            end = (jnp.sum(_own(fed, lanes), axis=(0, 1), keepdims=True)
                   + jnp.sum(_own(carried, lanes), axis=(0, 1), keepdims=True))
            ends = jnp.where(head == h, end, ends)
        dxd = jnp.concatenate(dx, axis=0)
        for w, lanes in enumerate(ch.lanes):
            ddt = jnp.sum(_own(dxd * x32, lanes), axis=1, keepdims=True)
            dt_sums = jnp.where(head == t * ch.per + w, ddt, dt_sums)
        dx_ref[:, tile] = (dxd * steps).astype(dx_ref.dtype)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, Q), 1) == Q - 1
    dacs_ref[...] += row_sums.T + jnp.where(last, ends.T, 0.0)
    ddt_ref[...] = dt_sums.T
    dcb = dcb_ref[...].astype(dtype)
    dc_ref[...] = dc + jnp.dot(dcb, bm, preferred_element_type=_F32)
    db_ref[...] = db + jax.lax.dot_general(dcb, cm, _TN,
                                           preferred_element_type=_F32)


def _calls(x_shape, b_shape, H, G, docs, reverse):
    """Grid (batch, group, block of `HEADS` heads, chunk) and the operands'
    blocks (the kernels see them without the leading dimensions): the
    block's (Q, HEADS * P) lanes of x, y and their gradients, its rows of dt
    and of cumulated decays, the group's (Q, N) lanes of B and C, the (N, HEADS *
    P) state entering the chunk, the block's d B and d C, and with documents
    the chunk's ids as a row and a column and the id of the document the
    entering state belongs to (on every lane). `reverse` walks the chunks
    from the last."""
    Bt, nc, Q, HP = x_shape
    P, N = HP // H, b_shape[-1] // G
    blocks = H // G // HEADS
    W = HEADS * P
    zero = np.int32(0)          # (a Python 0 is an i64 under jax_enable_x64)
    last = np.int32(nc - 1)

    def chunk(z):
        return last - z if reverse else z
    lanes = pl.BlockSpec((None, None, Q, W),
                         lambda b, g, k, z: (b, chunk(z), zero, g * blocks + k))
    rows = pl.BlockSpec((None, None, HEADS, Q),
                        lambda b, g, k, z: (b, chunk(z), g * blocks + k, zero))
    group = pl.BlockSpec((None, None, Q, N),
                         lambda b, g, k, z: (b, chunk(z), zero, g))
    state = pl.BlockSpec((None, None, N, W),
                         lambda b, g, k, z: (b, chunk(z), zero, g * blocks + k))
    per_block = pl.BlockSpec(
        (None, None, None, Q, N),
        lambda b, g, k, z: (b, chunk(z), g * blocks + k, zero, zero))
    doc_specs = []
    if docs:
        doc_specs = [pl.BlockSpec((None, None, 1, Q),
                                  lambda b, g, k, z: (b, chunk(z), zero, zero)),
                     pl.BlockSpec((None, None, Q, 1),
                                  lambda b, g, k, z: (b, chunk(z), zero, zero)),
                     pl.BlockSpec((None, None, 1, T),
                                  lambda b, g, k, z: (b, chunk(z), zero, zero))]
    grid = (Bt, G, blocks, nc)
    return grid, lanes, rows, group, state, per_block, doc_specs, P, N, W


def _params():
    return pltpu.CompilerParams(dimension_semantics=(
        "parallel", "parallel", "parallel", "arbitrary"))


# One pallas_call a signature: its traced kernel is then kept, and the layers
# of a model that share the shapes trace each kernel once.
@functools.lru_cache(maxsize=None)
def _forward_call(x_shape, x_dtype, b_shape, H, G, docs, interpret):
    grid, lanes, rows, group, state, _, doc_specs, P, N, W = _calls(
        x_shape, b_shape, H, G, docs, False)
    Bt, nc = x_shape[:2]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, P=P, docs=docs),
        grid=grid, in_specs=[lanes, rows, rows, group, group] + doc_specs,
        out_specs=[lanes, state],
        out_shape=[jax.ShapeDtypeStruct(x_shape, _F32),
                   jax.ShapeDtypeStruct((Bt, nc, N, x_shape[-1]), _F32)],
        scratch_shapes=[pltpu.VMEM((N, W), _F32)],
        compiler_params=_params(), name="ssd_scan_fwd", interpret=interpret)


@functools.lru_cache(maxsize=None)
def _backward_call(x_shape, x_dtype, b_shape, H, G, docs, interpret):
    grid, lanes, rows, group, state, per_block, doc_specs, P, N, W = _calls(
        x_shape, b_shape, H, G, docs, True)
    Bt, nc, Q = x_shape[:3]
    rows_shape = (Bt, nc, H, Q)
    block_shape = (Bt, nc, H // HEADS, Q, N)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, P=P, docs=docs),
        grid=grid,
        in_specs=[lanes, rows, rows, group, group, state, lanes] + doc_specs,
        out_specs=[lanes, rows, rows, per_block, per_block],
        out_shape=[jax.ShapeDtypeStruct(x_shape, x_dtype),
                   jax.ShapeDtypeStruct(rows_shape, _F32),
                   jax.ShapeDtypeStruct(rows_shape, _F32),
                   jax.ShapeDtypeStruct(block_shape, _F32),
                   jax.ShapeDtypeStruct(block_shape, _F32)],
        scratch_shapes=[pltpu.VMEM((N, W), _F32), pltpu.VMEM((Q, Q), _F32)],
        compiler_params=_params(), name="ssd_scan_bwd", interpret=interpret)


def _docs(docs):
    """The chunk's document ids as a row and as a column, and the id of the
    document the state entering the chunk belongs to (the previous chunk's
    last; nothing enters chunk 0) on every lane of a row; or nothing."""
    if docs is None:
        return ()
    n_in = jnp.pad(docs[:, :-1, -1], ((0, 0), (1, 0)))
    n_in = jnp.broadcast_to(n_in[:, :, None, None], n_in.shape + (1, T))
    return docs[:, :, None, :], docs[:, :, :, None], n_in


def _forward(x, dt, acs, b, c, docs, G, interpret):
    call = _forward_call(x.shape, x.dtype, b.shape, acs.shape[2], G,
                         docs is not None, interpret)
    return call(x, dt, acs, b, c, *_docs(docs))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan(x, dt, acs, b, c, docs, G, interpret):
    return _forward(x, dt, acs, b, c, docs, G, interpret)[0]


def _scan_fwd(x, dt, acs, b, c, docs, G, interpret):
    y, entering = _forward(x, dt, acs, b, c, docs, G, interpret)
    return y, (x, dt, acs, b, c, docs, entering)


def _scan_bwd(G, interpret, residuals, dy):
    x, dt, acs, b, c, docs, entering = residuals
    call = _backward_call(x.shape, x.dtype, b.shape, acs.shape[2], G,
                          docs is not None, interpret)
    dx, ddt, dacs, db, dc = call(x, dt, acs, b, c, entering, dy,
                                 *_docs(docs))

    def by_group(d):      # (Bt, nc, blocks of all groups, Q, N) -> b's shape
        Bt, nc, _, Q, N = d.shape
        d = d.reshape(Bt, nc, G, -1, Q, N).sum(axis=3)
        return jnp.moveaxis(d, 2, 3).reshape(b.shape).astype(b.dtype)
    return dx, ddt, dacs, by_group(db), by_group(dc), None


_scan.defvjp(_scan_fwd, _scan_bwd)


def scan(x, dt, acs, b, c, groups, docs=None, interpret=False):
    """The chunked scan (module docstring; shapes `takes` allows): x (Bt, nc,
    Q, H * P) in any float dtype; dt and acs (Bt, nc, H, Q) f32, a row a
    head; b, c (Bt, nc, Q, groups * N) in the products' dtype; docs (Bt, nc,
    Q) int32 or None -> y (Bt, nc, Q, H * P) f32."""
    return _scan(x, dt, acs, b, c, docs, groups, interpret)
