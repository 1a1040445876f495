"""Plain NemotronH training step (Nemotron-3-Nano's hybrid of Mamba-2, grouped-query
attention and routed experts): float32 `jax.numpy`, matmuls at `highest`, no
kernels, no packing, no chunked scan, no dispatch. Imports nothing of the
program and takes nothing the program made: rows and tower come from
`benchmark.weights`.

x_0 = table[token]; every layer x + mixer(RMSNorm(x)), eps from the config;
the pattern's letters pick the mixer:
  M  in_proj -> [z | xBC | dt]; xBC = SiLU(causal depthwise conv(xBC) + bias);
     x (heads x head_dim), B, C (groups x state); dt = softplus(dt + dt_bias);
     A = -exp(A_log); h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t;
     y_t = C_t . h_t + D x_t -- the recurrence, a `lax.scan` over time (in
     rematerialised blocks of time steps, so that its backward pass fits);
     y = grouped RMSNorm(y * SiLU(z)); out_proj.
  *  q, k, v projections, softmax(q k^T / sqrt(d) + causal mask) v with every
     key in the softmax (a block of queries at a time), o_proj. No rotary.
  E  s = sigmoid(x W_r) in f32; the top k of s + correction bias; weights =
     chosen s / their sum * routed_scaling_factor; every HELD expert
     (`n_routed_experts` of the file, offset `expert_offset`, of the router's
     `router_width`) runs on every token, times a dense mask of its weight;
     absent experts add nothing; plus the shared expert.
logits = RMSNorm(x) W_head in f32; loss = mean softmax cross-entropy against
the next token; dense Adagrad (acc += g^2; w -= lr g / (sqrt(acc) + eps)) on
every leaf and on the touched rows, duplicates of a token id summed first.

`precision`: "f32" the reference; "tower_fp8" feeds every matrix product of
activations float8_e4m3 inputs (the router stays f32, as the configuration
says); "table_bf16" keeps rows and their accumulators in bfloat16. `fault`:
"half_batch" (the second half of every sequence weightless), "no_routed" (the
routed experts' terms left out), "drop_eighth" (every eighth token dropped at
dispatch), "chunk_reset" (the SSM state zeroed at every chunk boundary),
"noncausal" (attention without its mask).
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

FAMILY = "nemotron_h"
CONTROLS = ("tower_fp8", "table_bf16")
FAULTS = ("half_batch", "no_routed", "drop_eighth", "chunk_reset", "noncausal")
HI = jax.lax.Precision.HIGHEST
SCAN_BLOCK = 64  # time steps of the recurrence rematerialised together


def pattern_of(cfg: Dict) -> str:
    return cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]


def tables_of(cfg: Dict) -> Dict[str, Dict]:
    return {"token": {"width": cfg["hidden_size"], "zero_cols": 0}}


def _mamba_dims(cfg):
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    bc = cfg["n_groups"] * cfg["ssm_state_size"]
    return inner, bc, inner + 2 * bc


def dense_leaves(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], object]]:
    """(path, shape, init) of every tower leaf; paths are the flax names.
    `init` is a kernel's N(0, init) stddev, or the name of a fixed start."""
    d = cfg["hidden_size"]
    inner, _, conv_dim = _mamba_dims(cfg)
    h = cfg["mamba_num_heads"]
    out = []
    for i, kind in enumerate(pattern_of(cfg)):
        p = f"layers_{i}/"
        m = p + "mixer/"
        out.append((p + "norm_scale", (d,), "ones"))
        if kind == "M":
            k = cfg["conv_kernel"]
            out += [(m + "in_proj/kernel", (d, inner + conv_dim + h), d ** -0.5),
                    (m + "conv_kernel", (k, conv_dim), k ** -0.5),
                    (m + "conv_bias", (conv_dim,), "zeros"),
                    (m + "dt_bias", (h,), "dt_bias"), (m + "A_log", (h,), "a_log"),
                    (m + "D", (h,), "ones"), (m + "norm_scale", (inner,), "ones"),
                    (m + "out_proj/kernel", (inner, d), inner ** -0.5)]
        elif kind == "*":
            q = cfg["num_attention_heads"] * cfg["head_dim"]
            kv = cfg["num_key_value_heads"] * cfg["head_dim"]
            out += [(m + "q_proj/kernel", (d, q), d ** -0.5),
                    (m + "k_proj/kernel", (d, kv), d ** -0.5),
                    (m + "v_proj/kernel", (d, kv), d ** -0.5),
                    (m + "o_proj/kernel", (q, d), q ** -0.5)]
        else:
            e, f, s = cfg["n_routed_experts"], cfg["moe_intermediate_size"], \
                cfg["moe_shared_expert_intermediate_size"]
            out += [(m + "router_kernel", (d, cfg["router_width"]), d ** -0.5),
                    (m + "router_correction_bias", (cfg["router_width"],), "zeros"),
                    (m + "experts_up", (e, d, f), d ** -0.5),
                    (m + "experts_down", (e, f, d), f ** -0.5),
                    (m + "shared_up", (d, s), d ** -0.5),
                    (m + "shared_down", (s, d), s ** -0.5)]
    out += [("norm_f_scale", (d,), "ones"),
            ("lm_head", (d, cfg["vocab_size"]), d ** -0.5)]
    return out


def leaf_groups(cfg: Dict) -> Dict[str, str]:
    """{leaf path: group}, the map both sides of the comparison sum by: `head`
    (final norm and head), and a layer `L<i>.M`, `L<i>.attn`, or `L<i>.router` /
    `.experts` / `.shared`; a block's own norm rides with its largest part."""
    kinds = pattern_of(cfg)
    out = {}
    for path, _, _ in dense_leaves(cfg):
        if not path.startswith("layers_"):
            out[path] = "head"
            continue
        layer, _, rest = path.partition("/")
        i = int(layer.split("_")[1])
        part = {"M": "M", "*": "attn"}.get(kinds[i]) or next(
            (k for k in ("router", "experts") if "mixer/" + k in rest), "shared")
        out[path] = f"L{i}.{part}"
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


def _fixed(kind: str, shape, cfg):
    n = shape[0]
    if kind == "zeros":
        return jnp.zeros(shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "a_log":  # A = -(1 .. H)
        return jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32))
    if kind == "dt_bias":  # softplus^-1 of time steps log-spaced over [min, max]
        dt = jnp.exp(jnp.linspace(math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"]),
                                  n, dtype=jnp.float32))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(kind)


def init_leaf(keys: Dict, cfg: Dict, path: str, shape, init) -> jax.Array:
    if isinstance(init, str):
        return _fixed(init, shape, cfg)
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


def _rms(x, scale, eps, groups=1):
    g = x.reshape(x.shape[:-1] + (groups, -1))
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True) + eps)
    return g.reshape(x.shape) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def recurrence(x, dt, A, B, C, *, reset_every: int = 0):
    """h_t = exp(dt_t A) h_{t-1} + dt_t B_t (x) x_t; y_t = C_t . h_t, step by
    step. x (Bt, L, H, P); dt (Bt, L, H); A (H,); B, C (Bt, L, G, N).
    `reset_every` > 0 zeroes the state at every multiple of it (a fault)."""
    Bt, L, H, P = x.shape
    G, N = B.shape[2:]
    r = H // G
    pad = (-L) % SCAN_BLOCK
    feed = [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (x, dt, B, C)]
    pos = jnp.arange(L + pad)
    blocks = (L + pad) // SCAN_BLOCK
    feed = [jnp.moveaxis(t, 1, 0).reshape((blocks, SCAN_BLOCK) + t.shape[:1] + t.shape[2:]) for t in feed]
    feed.append(pos.reshape(blocks, SCAN_BLOCK))

    def step(h, f):
        xt, dtt, bt, ct, t = f
        if reset_every:
            h = jnp.where(t % reset_every == 0, 0.0, h)
        bh = jnp.repeat(bt, r, axis=1)                       # (Bt, H, N)
        ch = jnp.repeat(ct, r, axis=1)
        h = h * jnp.exp(dtt * A)[..., None, None] + (dtt[..., None] * xt)[..., None] * bh[:, :, None, :]
        return h, jnp.sum(h * ch[:, :, None, :], axis=-1)

    @jax.checkpoint
    def block(h, f):
        return jax.lax.scan(step, h, f)

    h0 = jnp.zeros((Bt, H, P, N), jnp.float32)
    _, y = jax.lax.scan(block, h0, tuple(feed))
    return jnp.moveaxis(y.reshape((L + pad, Bt, H, P)), 0, 1)[:, :L]


def mamba(p, x, cfg, precision, fault):
    inner, bc, conv_dim = _mamba_dims(cfg)
    h, hd, g, n, k = (cfg["mamba_num_heads"], cfg["mamba_head_dim"], cfg["n_groups"],
                      cfg["ssm_state_size"], cfg["conv_kernel"])
    zxbcdt = _mm("bsd,de->bse", x, p["in_proj/kernel"], precision)
    z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
    s = xbc.shape[1]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = _silu(sum(padded[:, j:j + s] * p["conv_kernel"][j] for j in range(k)) + p["conv_bias"])
    xs, bm, cm = jnp.split(xbc, [inner, inner + bc], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    xh = xs.reshape(xs.shape[:2] + (h, hd))
    y = recurrence(xh, dt, -jnp.exp(p["A_log"]), bm.reshape(bm.shape[:2] + (g, n)),
                   cm.reshape(cm.shape[:2] + (g, n)),
                   reset_every=cfg["chunk_size"] if fault == "chunk_reset" else 0)
    y = (y + xh * p["D"][:, None]).reshape(xs.shape)
    y = _rms(y * _silu(z), p["norm_scale"], cfg["layer_norm_epsilon"], groups=g)
    return _mm("bse,ed->bsd", y, p["out_proj/kernel"], precision)


def attention(p, x, cfg, precision, fault, block=512):
    b, s, _ = x.shape
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = _mm("bsd,de->bse", x, p["q_proj/kernel"], precision).reshape(b, s, hq, d)
    k = _mm("bsd,de->bse", x, p["k_proj/kernel"], precision).reshape(b, s, hkv, d)
    v = _mm("bsd,de->bse", x, p["v_proj/kernel"], precision).reshape(b, s, hkv, d)
    k, v = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, v))

    @jax.checkpoint
    def rows(qb, lo):
        sc = _mm("bqhd,bkhd->bhqk", qb, k, precision) / math.sqrt(d)
        if fault != "noncausal":
            sc = jnp.where((lo + jnp.arange(qb.shape[1]))[:, None] >= jnp.arange(s)[None, :], sc, -jnp.inf)
        return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v, precision)

    o = jnp.concatenate([rows(q[:, lo:lo + block], lo) for lo in range(0, s, block)], axis=1)
    return _mm("bse,ed->bsd", o.reshape(b, s, hq * d), p["o_proj/kernel"], precision)


def _relu2_mlp(x, up, down, precision):
    return _mm("tf,fd->td", jnp.square(jax.nn.relu(_mm("td,df->tf", x, up, precision))), down, precision)


def experts(p, x, cfg, precision, fault):
    """The shared expert's term plus those of the experts held:
    [expert_offset, expert_offset + n_routed_experts) of `router_width`."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    first, count = cfg["expert_offset"], cfg["n_routed_experts"]
    score = jax.nn.sigmoid(jnp.matmul(xt, p["router_kernel"], precision=HI))
    _, chosen = jax.lax.top_k(score + jax.lax.stop_gradient(p["router_correction_bias"]),
                              cfg["num_experts_per_tok"])
    gate = jnp.take_along_axis(score, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    gate = gate * cfg["routed_scaling_factor"]
    out = _relu2_mlp(xt, p["shared_up"], p["shared_down"], precision)
    if fault != "no_routed":
        keep = jnp.arange(b * s) % 8 != (7 if fault == "drop_eighth" else 8)
        for e in range(count):  # a dense mask: this expert's weight for every token (0 where not chosen)
            w = jnp.sum(jnp.where((chosen == first + e) & keep[:, None], gate, 0.0), axis=-1)
            out = out + w[:, None] * _relu2_mlp(xt, p["experts_up"][e], p["experts_down"][e], precision)
    return out.reshape(b, s, d)


def pairs_held(p, x, cfg):
    """How many (token, choice) pairs the router sends the experts held."""
    first, count = cfg["expert_offset"], cfg["n_routed_experts"]
    score = jax.nn.sigmoid(jnp.matmul(x.reshape(-1, x.shape[-1]), p["router_kernel"], precision=HI))
    _, chosen = jax.lax.top_k(score + p["router_correction_bias"], cfg["num_experts_per_tok"])
    return jnp.sum((chosen >= first) & (chosen < first + count))


MIXERS = {"M": mamba, "*": attention, "E": experts}


def _sub(dense_p: Dict, prefix: str) -> Dict:
    return {k[len(prefix):]: v for k, v in dense_p.items() if k.startswith(prefix)}


def forward(dense_p, rows, cfg, precision="f32", fault=""):
    """-> (logits (B, S, V), the pairs routed to held experts in each E layer)."""
    x, pairs = rows, []
    for i, kind in enumerate(pattern_of(cfg)):
        def layer(x, lp, kind=kind):
            h = _rms(x, lp["norm_scale"], cfg["layer_norm_epsilon"])
            sent = pairs_held(_sub(lp, "mixer/"), h, cfg) if kind == "E" else None
            return x + MIXERS[kind](_sub(lp, "mixer/"), h, cfg, precision, fault), sent
        x, sent = jax.checkpoint(layer)(x, _sub(dense_p, f"layers_{i}/"))
        if kind == "E":
            pairs.append(sent)
    x = _rms(x, dense_p["norm_f_scale"], cfg["layer_norm_epsilon"])
    return _mm("bsd,dv->bsv", x, dense_p["lm_head"], precision), jnp.stack(pairs) if pairs else jnp.zeros((0,))


def logits_fn(dense_p, rows, cfg, precision="f32", fault=""):
    return forward(dense_p, rows, cfg, precision, fault)[0]


def xent(logits, labels, weight):
    per = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(per * weight) / jnp.sum(weight)


def _adagrad(w, acc, g, cfg):
    acc = acc + g * g
    return w - cfg["learning_rate"] * g / (jnp.sqrt(acc) + cfg["adagrad_epsilon"]), acc


def _store(x, precision):
    """bfloat16 storage of rows and accumulators (`reduce_precision`: a convert
    there and back is a pair the compiler may drop, and on the chip it does)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) if precision == "table_bf16" else x


def follow(seed: int, cfg: Dict, chips: int, ids: np.ndarray, idx: np.ndarray, labels: np.ndarray,
           masks: np.ndarray, *, precision: str = "f32", fault: str = "") -> Dict:
    """Follow the K stacked steps from the seed. `ids` (N,) the sorted unique
    token ids padded to a fixed N; `idx` (K, B, S) positions into it; `labels`
    (K, B, S); `masks` (3, N) as `reference/deepfm.py` has them.
    -> losses (K,); `pairs_held` (K, expert layers); per leaf GROUP (`leaf_groups`) and per table four sums of
    squares: the gradients Adagrad received (acc_end - acc_start), those on
    the rows only step 1 touches, the parameters' change, and that change on
    the rows only the first three steps touch. One jitted step at a time (the
    state donated), then one jitted summary that makes the start values again,
    leaf by leaf: 8 GB of float32 state fit beside nothing else."""
    del chips  # one program on one chip: nothing is summed across workers
    acc0 = cfg["adagrad_initial_accumulator"]
    seq = idx.shape[2]
    weight = np.ones(idx.shape[1:], np.float32)
    if fault == "half_batch":
        weight = weight * (np.arange(seq) < seq // 2)
    keys = make_keys(seed, cfg)

    def start(keys, ids):
        dense = init_dense(keys, cfg)
        rows = {n: _store(r, precision) for n, r in init_rows(keys, cfg, ids).items()}
        return (dense, {n: jnp.full_like(p, acc0) for n, p in dense.items()},
                rows, {n: _store(jnp.full_like(r, acc0), precision) for n, r in rows.items()})

    def step(state, ix, y, weight):
        dense_p, dacc, rows, accs = state

        def loss_fn(dense_p, pulled):
            logits, pairs = forward(dense_p, pulled["token"], cfg, precision, fault)
            return xent(logits, y, weight), pairs

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
            s = sums(init_leaf(keys, cfg, path, shape, init).reshape(1, -1), dense_k[path].reshape(1, -1),
                     dacc_k[path].reshape(1, -1), 0.0, 0.0)
            dense[groups[path]] = dense.get(groups[path], 0.0) + s
        rows0 = {n: _store(r, precision) for n, r in init_rows(keys, cfg, ids).items()}
        return {"dense": dense,
                "tables": {n: sums(rows0[n], rows_k[n], accs_k[n], masks[1], masks[2]) for n in rows0}}

    state = jax.jit(start)(keys, ids)
    step = jax.jit(step, donate_argnums=0)
    per_step = []
    for k in range(idx.shape[0]):
        state, loss_and_pairs = step(state, idx[k], labels[k], weight)
        per_step.append(loss_and_pairs)
    out = jax.jit(summary)(state, keys, ids, masks)
    out["losses"] = jnp.stack([l for l, _ in per_step])
    out["pairs_held"] = jnp.stack([p for _, p in per_step])  # (K, expert layers)
    return out
