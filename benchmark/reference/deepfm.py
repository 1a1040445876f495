"""Plain DeepFM training step: float32 `jax.numpy`, matmuls at `highest`, no
kernels, no packing, no exchange. Imports nothing of the program and takes
nothing the program made: its rows and tower come from `benchmark.weights`.

The model (Guo et al. 2017, as the reference system's DeepCTR benchmark builds
it): logit = sum_f w_f + <dense, k0> + b0                  (first order)
            + 0.5 * sum_d[(sum_f v_fd)^2 - sum_f v_fd^2]   (FM second order)
            + MLP(concat(dense, v.flatten()))              (deep)
loss = mean sigmoid cross-entropy; Adagrad (acc += g^2; w -= lr*g/(sqrt(acc)+eps))
on the tower and on the touched rows, duplicates of a row summed before the update.
Across `chips` workers each takes the mean over its own rows and gradients are
SUMMED (the configuration's `dense_reduce: sum`): the same as one worker whose
gradients are scaled by `chips`; the reported loss is the global mean.

Tables are held compact: only the rows the followed steps touch (`ids` sorted,
`idx` the position of every batch entry in it), so it fits beside nothing else.

`precision` selects the control: "f32" is the reference itself; "table_bf16"
stores rows and accumulators in bfloat16 (rounded after every update);
"tower_fp8" feeds every tower matmul float8_e4m3 inputs. `fault` plants a fault
of the timed path: "half_batch" leaves out the second half of every worker's
rows and takes the mean over the rest; "no_exchange" reads rows another worker
owns (id % chips) as zero and drops their gradients.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

FAMILY = "deepfm"


def tables_of(cfg: Dict) -> Dict[str, Dict]:
    """name -> {width, zero_cols}. Folded: one table of dim+1 columns (column 0
    the first-order weight). Split: latent table and a width-1 first-order table."""
    dim = cfg["embedding_dim"]
    if cfg["first_order"] == "fold":
        return {"categorical": {"width": dim + 1, "zero_cols": 1}}
    return {"categorical": {"width": dim, "zero_cols": 0},
            "first_order": {"width": 1, "zero_cols": 1}}


def dense_leaves(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], float]]:
    """(path, shape, init stddev) of every tower leaf; paths are the flax names."""
    f_in = cfg["num_dense"] + cfg["num_sparse"] * cfg["embedding_dim"]
    out = [("Dense_0/bias", (1,), 0.0),
           ("Dense_0/kernel", (cfg["num_dense"], 1), cfg["num_dense"] ** -0.5)]
    widths = list(cfg["hidden"]) + [1]
    for i, w in enumerate(widths):
        out.append((f"MLP_0/Dense_{i}/bias", (w,), 0.0))
        out.append((f"MLP_0/Dense_{i}/kernel", (f_in, w), f_in ** -0.5))
        f_in = w
    return out


def make_keys(seed: int, cfg: Dict) -> Dict[str, np.uint32]:
    """The uint32 key of every leaf: traced arguments, so one program serves all seeds."""
    names = ["dense/" + p for p, _, _ in dense_leaves(cfg)] + ["tables/" + n for n in tables_of(cfg)]
    return {n: weights.stream_key(seed, n) for n in names}


def init_dense(keys: Dict, cfg: Dict) -> Dict[str, jax.Array]:
    return {path: (jnp.zeros(shape, jnp.float32) if std == 0.0 else
                   weights.dense_leaf(keys["dense/" + path], shape, std))
            for path, shape, std in dense_leaves(cfg)}


def init_rows(keys: Dict, cfg: Dict, ids) -> Dict[str, jax.Array]:
    return {name: weights.table_rows(keys["tables/" + name], ids,
                                     t["width"], cfg["table_init_stddev"], t["zero_cols"])
            for name, t in tables_of(cfg).items()}


def _fp8(x):
    q = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


def _matmul(x, k, precision):
    if precision == "tower_fp8":
        x, k = _fp8(x), _fp8(k)
    return jnp.matmul(x, k, precision=jax.lax.Precision.HIGHEST)


def logits_fn(dense_p, rows, dense_x, cfg, precision):
    if cfg["first_order"] == "fold":
        w, v = rows["categorical"][..., 0], rows["categorical"][..., 1:]
    else:
        w, v = rows["first_order"][..., 0], rows["categorical"]
    first = (jnp.sum(w, axis=-1) + _matmul(dense_x, dense_p["Dense_0/kernel"], precision)[:, 0]
             + dense_p["Dense_0/bias"][0])
    fm = 0.5 * jnp.sum(jnp.square(jnp.sum(v, axis=1)) - jnp.sum(jnp.square(v), axis=1), axis=-1)
    x = jnp.concatenate([dense_x, v.reshape(v.shape[0], -1)], axis=-1)
    n = len(cfg["hidden"]) + 1
    for i in range(n):
        x = _matmul(x, dense_p[f"MLP_0/Dense_{i}/kernel"], precision) + dense_p[f"MLP_0/Dense_{i}/bias"]
        if i < n - 1:
            x = jnp.maximum(x, 0.0)
    return first + fm + x[:, 0]


def _bce(logits, labels, weight):
    per = jnp.maximum(logits, 0.0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    return jnp.sum(per * weight) / jnp.sum(weight)


def _adagrad(w, acc, g, cfg):
    acc = acc + g * g
    return w - cfg["learning_rate"] * g / (jnp.sqrt(acc) + cfg["adagrad_epsilon"]), acc


def _store(x, precision):
    return x.astype(jnp.bfloat16).astype(jnp.float32) if precision == "table_bf16" else x


def follow(seed: int, cfg: Dict, chips: int, ids: np.ndarray, idx: np.ndarray,
           dense_x: np.ndarray, labels: np.ndarray, masks: np.ndarray, *,
           precision: str = "f32", fault: str = "") -> Dict:
    """Follow the K stacked steps from the seed. `ids` (N,) the sorted unique ids,
    padded to a fixed N so that one program serves all seeds; `idx` (K, B, F) positions into it, `dense_x` (K, B, D), `labels` (K, B).
    `masks` (3, N): all touched rows, the rows only step 1 touches, the rows only
    the first three steps touch.
    -> losses (K,), and per leaf four sums of squares: of the gradients Adagrad
    received (acc_end - acc_start), of those on the rows only step 1 touches (the
    first gradient, kept whole), of the parameters' change, and of that change on
    the rows only the first three steps touch (the change after three steps)."""
    acc0 = cfg["adagrad_initial_accumulator"]
    k_steps, batch, _ = idx.shape
    per = batch // chips
    weight = np.ones((batch,), np.float32)
    if fault == "half_batch":
        weight = (np.arange(batch) % per < per // 2).astype(np.float32)
    keep = np.ones(idx.shape, np.float32)
    if fault == "no_exchange":
        worker = (np.arange(batch) // per)[None, :, None]
        keep = (ids[idx] % chips == worker).astype(np.float32)

    def run(keys, ids, idx, dense_x, labels, weight, keep, masks):
        dense0 = init_dense(keys, cfg)
        rows0 = {n: _store(r, precision) for n, r in init_rows(keys, cfg, ids).items()}
        accs0 = {n: _store(jnp.full_like(r, acc0), precision) for n, r in rows0.items()}
        dacc0 = {n: jnp.full_like(p, acc0) for n, p in dense0.items()}

        def loss_fn(dense_p, pulled, x, y):
            logits = logits_fn(dense_p, pulled, x, cfg, precision)
            return _bce(logits, y, weight)

        def step(carry, feed):
            dense_p, dacc, rows, accs = carry
            ix, x, y, kp = feed
            pulled = {n: r[ix] * kp[..., None] for n, r in rows.items()}
            loss, (gd, gr) = jax.value_and_grad(loss_fn, argnums=(0, 1))(dense_p, pulled, x, y)
            scale = jnp.float32(chips)  # per-worker means, gradients summed over workers
            new_dense, new_dacc = {}, {}
            for n in dense_p:
                new_dense[n], new_dacc[n] = _adagrad(dense_p[n], dacc[n], gd[n] * scale, cfg)
            new_rows, new_accs = {}, {}
            for n in rows:
                g = jnp.zeros_like(rows[n]).at[ix].add(gr[n] * kp[..., None] * scale)
                w, a = _adagrad(rows[n], accs[n], g, cfg)
                new_rows[n], new_accs[n] = _store(w, precision), _store(a, precision)
            return (new_dense, new_dacc, new_rows, new_accs), loss

        (dense_k, dacc_k, rows_k, accs_k), losses = jax.lax.scan(
            step, (dense0, dacc0, rows0, accs0), (idx, dense_x, labels, keep))
        def sums(w0, wk, acck, m_first, m_early):
            g2 = jnp.sum(acck - acc0, axis=-1)
            d2 = jnp.sum(jnp.square(wk - w0), axis=-1)
            return jnp.stack([jnp.sum(g2), jnp.sum(g2 * m_first), jnp.sum(d2), jnp.sum(d2 * m_early)])

        return {"losses": losses,
                "dense": {n: sums(dense0[n].reshape(1, -1), dense_k[n].reshape(1, -1),
                                  dacc_k[n].reshape(1, -1), 0.0, 0.0) for n in dense0},
                "tables": {n: sums(rows0[n], rows_k[n], accs_k[n], masks[1], masks[2]) for n in rows0}}

    return jax.jit(run)(make_keys(seed, cfg), ids, idx, dense_x, labels, weight, keep, masks)
