"""Plain SDAR training step by block diffusion (the Qwen3-MoE decoder over a
noised and a clean copy of every sequence): float32 `jax.numpy`, matmuls at
`highest`, no kernels, no dispatch, no blockwise walk; ONE `lax.scan` over the
stacked layers, all alike. Imports nothing of the program and takes nothing
the program made: rows and tower come from `benchmark.weights`, the noise is
the driver's.

The stack sees [x_t ; x_0] (B, 2L), rows = table[token]; positions i mod L
(rotary angles made in float64 on the host). A layer, eps `rms_norm_eps`:
  attn     u = RMSNorm(x); q = u W_q, k = u W_k, v = u W_v (no bias), a head
           of `head_dim`; q, k RMSNorm'd over each head (scales of their own);
           rotary half-rotation over the whole head, theta `rope_theta`; keys
           and values repeated over the query heads of their group;
           softmax(q k^T / sqrt(head_dim) + M) v, M the block-diffusion mask
           written out in full for a block of 512 queries at a time: with b
           the denoising block (`block_length`) of a position in its half, a
           noisy query sees the noisy keys of its own b and the clean keys of
           b' < b; a clean query the clean keys of b' <= b; W_o; x += that.
  experts  w = RMSNorm(x); p = softmax(w W_r) over `router_width`, the top
           `num_experts_per_tok`, gates over their sum; every HELD expert
           (`num_experts` of the file from `expert_offset`) runs on every
           position, one after another, times a dense mask of its gate, SwiGLU;
           absent experts add nothing; no shared expert; x += the sum.
logits = RMSNorm(x[:, :L]) W_head (the noisy half); loss = sum_i weight_i
CE(logits_i, x_0,i) / (B L), weight 1 / t_b where masked, 0 elsewhere; dense
Adagrad on every leaf and on the touched rows, duplicates summed first.

`precision`: "f32" the reference; "tower_fp8" feeds every matrix product of
activations float8_e4m3 inputs (the router stays f32); "table_bf16" keeps rows
and their accumulators in bfloat16. `fault`: "half_batch" (the second half of
every sequence weightless), "no_routed" (the experts' terms left out),
"drop_eighth" (every eighth position dropped at dispatch), "answer_leak"
(noisy queries also see the clean keys of their own block), "no_clean_context"
(noisy queries see no clean key), "causal_concat" (a plain causal mask over the
doubled sequence), "unweighted" (no 1 / t: weight 1 where masked),
"shifted_labels" (the label of position i is x_0 at i + 1; the last weighs 0),
"no_qk_norm" (q and k not normed), "clean_positions_offset" (the clean half at
positions L..2L-1).
"""

from __future__ import annotations

import functools
import json
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

FAMILY = "sdar_moe"
CONTROLS = ("tower_fp8", "table_bf16")
FAULTS = ("half_batch", "no_routed", "drop_eighth", "answer_leak", "no_clean_context", "causal_concat",
          "unweighted", "shifted_labels", "no_qk_norm", "clean_positions_offset")
HI = jax.lax.Precision.HIGHEST
STACK = "layers/"  # every layer alike: their leaves stacked on a leading axis, one `lax.scan` over them


def tables_of(cfg: Dict) -> Dict[str, Dict]:
    return {"token": {"width": cfg["hidden_size"], "zero_cols": 0}}


def _layer_leaves(cfg: Dict, p: str) -> List[Tuple[str, Tuple[int, ...], object]]:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    a, m = p + "attn/", p + "moe/"
    return [(p + "attn_norm_scale", (d,), "ones"),
            (a + "q_proj/kernel", (d, hq), d ** -0.5), (a + "k_proj/kernel", (d, hkv), d ** -0.5),
            (a + "v_proj/kernel", (d, hkv), d ** -0.5),
            (a + "q_norm_scale", (hd,), "ones"), (a + "k_norm_scale", (hd,), "ones"),
            (a + "o_proj/kernel", (hq, d), hq ** -0.5),
            (p + "ffn_norm_scale", (d,), "ones"), (p + "router_kernel", (d, cfg["router_width"]), d ** -0.5),
            (m + "experts_gate", (e, d, f), d ** -0.5), (m + "experts_up", (e, d, f), d ** -0.5),
            (m + "experts_down", (e, f, d), f ** -0.5)]


def dense_leaves(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], object]]:
    """(path, shape, init) of every tower leaf; paths are the flax names.
    `init` is a kernel's N(0, init) stddev, or the name of a fixed start."""
    d = cfg["hidden_size"]
    out = []
    for i in range(cfg["num_hidden_layers"]):
        out += _layer_leaves(cfg, f"layers_{i}/")
    return out + [("norm_f_scale", (d,), "ones"), ("lm_head", (d, cfg["vocab_size"]), d ** -0.5)]


def leaf_groups(cfg: Dict) -> Dict[str, str]:
    """{leaf path: group}, the map both sides of the comparison sum by: `head`
    (final norm and head); a layer's `L<i>.attn` (with its norm), `L<i>.router`
    (with the expert sub-layer's norm), `L<i>.experts`."""
    out = {}
    for path, _, _ in dense_leaves(cfg):
        if not path.startswith("layers_"):
            out[path] = "head"
            continue
        layer, _, rest = path.partition("/")
        part = "attn" if rest.startswith("attn") else "experts" if rest.startswith("moe/") else "router"
        out[path] = f"L{layer.split('_')[1]}.{part}"
    return out


def group_sizes(cfg: Dict) -> Dict[str, int]:
    """{group: its number of elements}."""
    groups, out = leaf_groups(cfg), {}
    for path, shape, _ in dense_leaves(cfg):
        out[groups[path]] = out.get(groups[path], 0) + int(np.prod(shape))
    return out


def make_keys(seed: int, cfg: Dict) -> Dict[str, np.uint32]:
    names = ["dense/" + p for p, _, _ in dense_leaves(cfg)] + ["tables/" + n for n in tables_of(cfg)]
    return {n: weights.stream_key(seed, n) for n in names}


def init_leaf(keys: Dict, cfg: Dict, path: str, shape, init) -> jax.Array:
    if isinstance(init, str):
        return {"zeros": jnp.zeros, "ones": jnp.ones}[init](shape, jnp.float32)
    return weights.dense_leaf(keys["dense/" + path], shape, init)


def init_dense(keys: Dict, cfg: Dict) -> Dict[str, jax.Array]:
    return {path: init_leaf(keys, cfg, path, shape, init) for path, shape, init in dense_leaves(cfg)}


def init_rows(keys: Dict, cfg: Dict, ids) -> Dict[str, jax.Array]:
    return {name: weights.table_rows(keys["tables/" + name], ids, t["width"],
                                     cfg["table_init_stddev"], t["zero_cols"])
            for name, t in tables_of(cfg).items()}


# -- the model ----------------------------------------------------------------

def _fp8(x):
    q = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


def _mm(spec, a, b, precision):
    if precision == "tower_fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rotary(x, positions: np.ndarray, theta: float):
    """Half-rotation over every dim of each head: x (B, S, H, d); the angles
    pos * theta^(-2i/d) as float64 tables made on the host."""
    half = x.shape[-1] // 2
    ang = positions.astype(np.float64)[:, None] * theta ** (-np.arange(half, dtype=np.float64) / half)[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def seen(qpos, kpos, half: int, bk: int, fault: str = ""):
    """The block-diffusion mask (module docstring) over broadcasting position
    arrays of the doubled sequence, or a fault's."""
    if fault == "causal_concat":
        return kpos <= qpos
    q_noisy, k_noisy = qpos < half, kpos < half
    qb = jnp.where(q_noisy, qpos, qpos - half) // bk
    kb = jnp.where(k_noisy, kpos, kpos - half) // bk
    clean_before = (kb <= qb) if fault == "answer_leak" else (kb < qb)
    if fault == "no_clean_context":
        clean_before = jnp.zeros_like(clean_before)
    noisy = jnp.where(k_noisy, kb == qb, clean_before)
    return jnp.where(q_noisy, noisy, ~k_noisy & (kb <= qb))


def attention(p, u, cfg, precision, fault, block=512):
    b, s, _ = u.shape
    h, g, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    half, eps = s // 2, cfg["rms_norm_eps"]
    q = _mm("bsd,de->bse", u, p["q_proj/kernel"], precision).reshape(b, s, h, d)
    k = _mm("bsd,de->bse", u, p["k_proj/kernel"], precision).reshape(b, s, g, d)
    v = _mm("bsd,de->bse", u, p["v_proj/kernel"], precision).reshape(b, s, g, d)
    if fault != "no_qk_norm":
        q, k = _rms(q, p["q_norm_scale"], eps), _rms(k, p["k_norm_scale"], eps)
    pos = np.arange(s) if fault == "clean_positions_offset" else np.arange(s) % half
    q, k = _rotary(q, pos, float(cfg["rope_theta"])), _rotary(k, pos, float(cfg["rope_theta"]))
    k, v = (jnp.repeat(t, h // g, axis=2) for t in (k, v))
    bk = cfg["block_length"]

    @jax.checkpoint
    def rows(qb, lo):
        sc = _mm("bqhd,bkhd->bhqk", qb, k, precision) / math.sqrt(d)
        mask = seen((lo + jnp.arange(qb.shape[1]))[:, None], jnp.arange(s)[None, :], half, bk, fault)
        sc = jnp.where(mask, sc, -jnp.inf)
        return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v, precision)

    block = min(block, s)
    whole = s // block * block  # the blocks of equal size as one loop, what is left as a last block
    o = jax.lax.map(lambda a: rows(*a), (q[:, :whole].reshape(b, -1, block, h, d).swapaxes(0, 1),
                                         jnp.arange(0, whole, block)))
    o = o.swapaxes(0, 1).reshape(b, whole, h, d)
    if whole < s:
        o = jnp.concatenate([o, rows(q[:, whole:], whole)], axis=1)
    return _mm("bse,ed->bsd", o.reshape(b, s, h * d), p["o_proj/kernel"], precision)


def route(p, wt, cfg):
    """wt (T, D) -> (chosen (T, k), gates (T, k)): softmax over the router's
    outputs in f32, the top k, renormalised."""
    prob = jax.nn.softmax(jnp.matmul(wt, p["router_kernel"], precision=HI), axis=-1)
    gate, chosen = jax.lax.top_k(prob, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
    return chosen, gate


def experts(p, wt, chosen, gate, cfg, precision, fault):
    """The terms of the experts held: [expert_offset, expert_offset +
    num_experts) of `router_width`; (T, D)."""
    first, count = cfg["expert_offset"], cfg["num_experts"]
    keep = jnp.arange(wt.shape[0]) % 8 != (7 if fault == "drop_eighth" else 8)

    @jax.checkpoint  # keeps the expert's weights alone; its hidden states are made again
    def term(held):  # a dense mask: this expert's gate for every position (0 where not chosen)
        e, w_gate, w_up, w_down = held
        w = jnp.sum(jnp.where((chosen == first + e) & keep[:, None], gate, 0.0), axis=-1)
        gx = _mm("td,df->tf", wt, w_gate, precision)
        hid = gx * jax.nn.sigmoid(gx) * _mm("td,df->tf", wt, w_up, precision)
        return w[:, None] * _mm("tf,fd->td", hid, w_down, precision)

    def add(out, held):
        return out + term(held), None

    return jax.lax.scan(add, jnp.zeros_like(wt), (jnp.arange(count), p["moe/experts_gate"], p["moe/experts_up"],
                                                  p["moe/experts_down"]))[0]


def decoder_layer(lp, x, cfg, precision, fault):
    """-> (x, the pairs routed to held experts)."""
    eps = cfg["rms_norm_eps"]
    b, s, d = x.shape
    x = x + attention({k[5:]: v for k, v in lp.items() if k.startswith("attn/")},
                      _rms(x, lp["attn_norm_scale"], eps), cfg, precision, fault)
    wt = _rms(x, lp["ffn_norm_scale"], eps).reshape(b * s, d)
    chosen, gate = route(lp, wt, cfg)
    lo = cfg["expert_offset"]
    sent = jnp.sum((chosen >= lo) & (chosen < lo + cfg["num_experts"]))
    y = jnp.zeros_like(wt) if fault == "no_routed" else experts(lp, wt, chosen, gate, cfg, precision, fault)
    return x + y.reshape(b, s, d), sent


def _sub(dense_p: Dict, prefix: str) -> Dict:
    return {k[len(prefix):]: v for k, v in dense_p.items() if k.startswith(prefix)}


def stacked(dense_p: Dict, cfg: Dict) -> Dict:
    """The leaves by the program's paths -> the layout `forward` runs on: every
    layer's leaves stacked on a leading layer axis under `STACK` (one compiled
    layer body). A dict that has the layout already comes back as it is."""
    if any(k.startswith(STACK) for k in dense_p):
        return dense_p
    out = {k: v for k, v in dense_p.items() if not k.startswith("layers_")}
    for name in _sub(dense_p, "layers_0/"):
        out[STACK + name] = jnp.stack([dense_p[f"layers_{i}/{name}"] for i in range(cfg["num_hidden_layers"])])
    return out


def forward(dense_p, rows, cfg, precision="f32", fault=""):
    """rows (B, 2L, D) of [x_t ; x_0] -> (the noisy half's logits (B, L, V),
    the pairs routed to held experts in each layer)."""
    p = stacked(dense_p, cfg)
    layer = jax.checkpoint(lambda x, lp: decoder_layer(lp, x, cfg, precision, fault))
    x, pairs = jax.lax.scan(layer, rows, _sub(p, STACK))
    half = rows.shape[1] // 2
    logits = _mm("bsd,dv->bsv", _rms(x[:, :half], p["norm_f_scale"], cfg["rms_norm_eps"]), p["lm_head"],
                 precision)
    return logits, pairs


def diffusion_loss(logits, labels, weight, fault=""):
    """sum_i weight_i CE(logits_i, label_i) / (B L), or a fault's."""
    if fault == "shifted_labels":
        labels = jnp.pad(labels[:, 1:], ((0, 0), (0, 1)))
        weight = weight * (jnp.arange(labels.shape[1]) < labels.shape[1] - 1)
    if fault == "unweighted":
        weight = (weight > 0).astype(jnp.float32)
    if fault == "half_batch":
        weight = weight * (jnp.arange(labels.shape[1]) < labels.shape[1] // 2)
    per = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(per * weight) / labels.size


def _adagrad(w, acc, g, cfg):
    acc = acc + g * g
    return w - cfg["learning_rate"] * g / (jnp.sqrt(acc) + cfg["adagrad_epsilon"]), acc


def _store(x, precision):
    """bfloat16 storage of rows and accumulators (`reduce_precision`: a convert
    there and back is a pair the compiler may drop, and on the chip it does)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) if precision == "table_bf16" else x


@functools.lru_cache(maxsize=2)
def _programs(cfg_json: str, precision: str, fault: str):
    """The jitted start, step and summary of one (configuration, precision,
    fault), kept for the next call, so that a checker's seeds share a compile;
    two at a time (the reference and one control or fault)."""
    cfg = json.loads(cfg_json)
    acc0 = cfg["adagrad_initial_accumulator"]

    def start(keys, ids):
        dense = stacked(init_dense(keys, cfg), cfg)
        rows = {n: _store(r, precision) for n, r in init_rows(keys, cfg, ids).items()}
        return (dense, {n: jnp.full_like(p, acc0) for n, p in dense.items()},
                rows, {n: _store(jnp.full_like(r, acc0), precision) for n, r in rows.items()})

    def step(state, ix, y, weight):
        dense_p, dacc, rows, accs = state

        def loss_fn(dense_p, pulled):
            logits, pairs = forward(dense_p, pulled["token"], cfg, precision, fault)
            return diffusion_loss(logits, y, weight, fault), pairs

        pulled = {n: r[ix] for n, r in rows.items()}
        (loss, pairs), (gd, gr) = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(dense_p, pulled)
        new_dense, new_dacc, new_rows, new_accs = {}, {}, {}, {}
        for n in dense_p:
            new_dense[n], new_dacc[n] = _adagrad(dense_p[n], dacc[n], gd[n], cfg)
        for n in rows:
            g = jnp.zeros_like(rows[n]).at[ix].add(gr[n])
            w, a = _adagrad(rows[n], accs[n], g, cfg)
            new_rows[n], new_accs[n] = _store(w, precision), _store(a, precision)
        return (new_dense, new_dacc, new_rows, new_accs), (loss, pairs)

    def sums(w0, wk, acck, m_first, m_early):
        g2 = jnp.sum(acck - acc0, axis=-1)
        d2 = jnp.sum(jnp.square(wk - w0), axis=-1)
        return jnp.stack([jnp.sum(g2), jnp.sum(g2 * m_first), jnp.sum(d2), jnp.sum(d2 * m_early)])

    def summary(state, keys, ids, masks):
        dense_k, dacc_k, rows_k, accs_k = state
        groups = leaf_groups(cfg)
        dense: Dict[str, jax.Array] = {}
        for path, shape, init in dense_leaves(cfg):
            layer, _, name = path.partition("/")
            if path in dense_k:
                wk, ak = dense_k[path], dacc_k[path]
            else:  # a layer's leaf: its slice of the stack
                wk, ak = (t[STACK + name][int(layer.split("_")[1])] for t in (dense_k, dacc_k))
            s = sums(init_leaf(keys, cfg, path, shape, init).reshape(1, -1), wk.reshape(1, -1),
                     ak.reshape(1, -1), 0.0, 0.0)
            dense[groups[path]] = dense.get(groups[path], 0.0) + s
        rows0 = {n: _store(r, precision) for n, r in init_rows(keys, cfg, ids).items()}
        return {"dense": dense,
                "tables": {n: sums(rows0[n], rows_k[n], accs_k[n], masks[1], masks[2]) for n in rows0}}

    return jax.jit(start), jax.jit(step, donate_argnums=0), jax.jit(summary)


def follow(seed: int, cfg: Dict, chips: int, ids: np.ndarray, idx: np.ndarray, labels: np.ndarray,
           masks: np.ndarray, *, weight: np.ndarray, precision: str = "f32", fault: str = "") -> Dict:
    """Follow the K stacked steps from the seed. `ids` (N,) the sorted unique
    token ids padded to a fixed N; `idx` (K, B, 2L) positions into it of [x_t ;
    x_0]; `labels` (K, B, L) x_0; `weight` (K, B, L) the noisy half's loss
    weights; `masks` (3, N) as `reference/deepfm.py` has them. -> losses (K,);
    `pairs_held` (K, layers); per leaf GROUP (`leaf_groups`) and per table four
    sums of squares: the gradients Adagrad received (acc_end - acc_start),
    those on the rows only step 1 touches, the parameters' change, and that
    change on the rows only the first three steps touch. One jitted step at a
    time (the state donated), then one jitted summary that makes the start
    values again, leaf by leaf."""
    del chips  # one program on one chip: nothing is summed across workers
    keys = make_keys(seed, cfg)
    start, step, summary = _programs(json.dumps(cfg, sort_keys=True), precision, fault)
    state = start(keys, ids)
    per_step = []
    for k in range(idx.shape[0]):
        state, out = step(state, idx[k], labels[k], np.asarray(weight[k], np.float32))
        per_step.append(out)
    out = summary(state, keys, ids, masks)
    out["losses"] = jnp.stack([o[0] for o in per_step])
    out["pairs_held"] = jnp.stack([o[1] for o in per_step])  # (K, layers)
    return out
