"""Plain Ouro training step (a looped language model, arXiv:2510.25741: a dense
decoder stack walked `total_ut_steps` times over the same weights, an exit
gate a pass, the expected loss over the exits through one head): float32
`jax.numpy`, matmuls at `highest`, no kernels, no packing, no blockwise
softmax; the passes a `lax.scan` whose body closes over the leaves, each pass
ONE `lax.scan` over the stacked layers (written as a Python loop over the
passes, autodiff hands back one gradient tree a pass to be summed at the end,
and the step needs 19.6 of the chip's 15.75 GiB; scanned, the transpose keeps
ONE running sum: 13.3). Imports nothing of the program and takes nothing the
program made: rows and tower come from `benchmark.weights`.

h_0 = table[token] (no multiplier). Pass t = 1..T, the SAME leaves in each:
  x = h_{t-1}; layer l: x += RMSNorm(Attn(RMSNorm(x; g1)); g2);
                        x += RMSNorm(MLP(RMSNorm(x; g3)); g4)  (eps `rms_norm_eps`)
  Attn  q, k, v = u W_q, u W_k, u W_v (heads of `head_dim`, no bias); rotary
        half-rotation on all `head_dim` dims of q and k: the pair (x_i,
        x_{i + d/2}) of position s turned by s * theta^(-2i/d), cos / sin
        tables made in float64 (`rope_scaling` null); softmax(q k^T / sqrt(d)
        + causal mask) v with every key in the softmax (a block of 512
        queries at a time, one loop over the blocks); W_o.
  MLP   W_down(silu(W_gate u) * (W_up u)), no bias.
  h_t = RMSNorm(x; g_f): the final norm inside the walk; the next pass reads it.
  exit  z_t = h_t W_head; l_t = the per-token cross-entropy of z_t against the
        next token (each exit's logits made, used and dropped in turn);
        lam_t = sigmoid(h_t . w_g + b_g).
p_t = lam_t prod_{s<t} (1 - lam_s) for t < T, p_T the rest of the mass;
loss = sum_tokens w [sum_t p_t l_t - beta H(p)] / sum_tokens w, H(p) = -sum_t
p_t log p_t, beta `exit_entropy_weight`. Dense Adagrad on every leaf (ONE
accumulator a leaf, the gradient summed over its T uses by autodiff) and on
the touched rows, duplicates summed first.

`precision`: "f32" the reference; "tower_fp8" feeds every matrix product of
activations float8_e4m3 inputs (the gate stays f32); "table_bf16" keeps rows
and their accumulators in bfloat16. `fault`: "half_batch" (the second half of
every sequence weightless), "noncausal" (attention without its mask),
"no_rope" (rotary positions left out), and the walk's own: "one_pass" (T = 1),
"last_use_only" (every pass but the last runs on `stop_gradient` copies of the
leaves: each leaf's gradient comes from its last use alone, what a walk that
did not sum over its uses would hand the optimizer), "no_loop_norm" (the next
pass reads the un-normed x; the exits alone read RMSNorm(x)), "no_post_norms"
(g2 and g4 left out), "last_exit_only" (loss = l_T), "flat_exit" (p = 1/T, the
gate ignored), "no_entropy" (beta = 0), "no_survival" (p_t = lam_t).
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

FAMILY = "ouro"
CONTROLS = ("tower_fp8", "table_bf16")
FAULTS = ("half_batch", "noncausal", "no_rope", "one_pass", "last_use_only", "no_loop_norm", "no_post_norms",
          "last_exit_only", "flat_exit", "no_entropy", "no_survival")
HI = jax.lax.Precision.HIGHEST
WALK = "walk/"           # the program's scope of the scanned pass
STACK = WALK + "layers/"  # the layers' leaves stacked on a leading axis: one `lax.scan` over them


def tables_of(cfg: Dict) -> Dict[str, Dict]:
    return {"token": {"width": cfg["hidden_size"], "zero_cols": 0}}


def _layer_leaves(cfg: Dict, p: str) -> List[Tuple[str, Tuple[int, ...], object]]:
    d, hd, i = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    a = p + "attn/"
    return [(p + "attn_norm_scale", (d,), "ones"),
            (a + "q_proj/kernel", (d, q), d ** -0.5), (a + "k_proj/kernel", (d, kv), d ** -0.5),
            (a + "v_proj/kernel", (d, kv), d ** -0.5), (a + "o_proj/kernel", (q, d), q ** -0.5),
            (p + "attn_post_norm_scale", (d,), "ones"), (p + "ffn_norm_scale", (d,), "ones"),
            (p + "mlp_gate", (d, i), d ** -0.5), (p + "mlp_up", (d, i), d ** -0.5),
            (p + "mlp_down", (i, d), i ** -0.5), (p + "ffn_post_norm_scale", (d,), "ones")]


def dense_leaves(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], object]]:
    """(path, shape, init) of every tower leaf; paths are the program's.
    `init` is a kernel's N(0, init) stddev, or the name of a fixed start."""
    d = cfg["hidden_size"]
    out = []
    for i in range(cfg["num_hidden_layers"]):
        out += _layer_leaves(cfg, f"{WALK}layers_{i}/")
    return out + [("norm_f_scale", (d,), "ones"), ("lm_head", (d, cfg["vocab_size"]), d ** -0.5),
                  ("exit_gate_kernel", (d,), d ** -0.5), ("exit_gate_bias", (1,), "zeros")]


def leaf_groups(cfg: Dict) -> Dict[str, str]:
    """{leaf path: group}, the map both sides of the comparison sum by: `head`,
    `gate` (w_g and b_g), `norm_f`, a layer's `L<i>.attn` (its projections, g1
    and g2) and `L<i>.mlp` (its three weights, g3 and g4)."""
    out = {"norm_f_scale": "norm_f", "lm_head": "head", "exit_gate_kernel": "gate", "exit_gate_bias": "gate"}
    for path, _, _ in dense_leaves(cfg):
        if path.startswith(WALK):
            layer, _, rest = path[len(WALK):].partition("/")
            out[path] = f"L{int(layer.split('_')[1])}." + ("attn" if rest.startswith("attn") else "mlp")
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


def rope(x, theta: float):
    """x (B, S, H, d): the pair (x_i, x_{i + d/2}) of position s turned by
    s * theta^(-2i/d); the angles' cos and sin made in float64."""
    seq, half = x.shape[1], x.shape[-1] // 2
    inv = float(theta) ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    cos, sin = (t.astype(np.float32)[None, :, None, :] for t in (np.cos(ang), np.sin(ang)))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, u, cfg, precision, fault, block=512):
    b, s, _ = u.shape
    h, g, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = _mm("bsd,de->bse", u, p["attn/q_proj/kernel"], precision).reshape(b, s, h, d)
    k = _mm("bsd,de->bse", u, p["attn/k_proj/kernel"], precision).reshape(b, s, g, d)
    v = _mm("bsd,de->bse", u, p["attn/v_proj/kernel"], precision).reshape(b, s, g, d)
    if fault != "no_rope":
        q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    k, v = (jnp.repeat(t, h // g, axis=2) for t in (k, v))  # a key/value head for each query head

    @jax.checkpoint
    def rows(qb, lo):
        sc = _mm("bqhd,bkhd->bhqk", qb, k, precision) / math.sqrt(d)
        if fault != "noncausal":
            sc = jnp.where((lo + jnp.arange(qb.shape[1]))[:, None] >= jnp.arange(s)[None, :], sc, -jnp.inf)
        return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v, precision)

    block = min(block, s)
    whole = s // block * block  # the blocks of equal size as one loop, what is left as a last block
    o = jax.lax.map(lambda a: rows(*a), (q[:, :whole].reshape(b, -1, block, h, d).swapaxes(0, 1),
                                         jnp.arange(0, whole, block)))
    o = o.swapaxes(0, 1).reshape(b, whole, h, d)
    if whole < s:
        o = jnp.concatenate([o, rows(q[:, whole:], whole)], axis=1)
    return _mm("bse,ed->bsd", o.reshape(b, s, h * d), p["attn/o_proj/kernel"], precision)


def mlp(p, u, precision):
    g = _mm("bsd,df->bsf", u, p["mlp_gate"], precision)
    return _mm("bsf,fd->bsd", g * jax.nn.sigmoid(g) * _mm("bsd,df->bsf", u, p["mlp_up"], precision),
               p["mlp_down"], precision)


def decoder_layer(lp, x, cfg, precision, fault):
    eps = cfg["rms_norm_eps"]
    post = (lambda y, scale: y) if fault == "no_post_norms" else (lambda y, scale: _rms(y, scale, eps))
    x = x + post(attention(lp, _rms(x, lp["attn_norm_scale"], eps), cfg, precision, fault),
                 lp["attn_post_norm_scale"])
    return x + post(mlp(lp, _rms(x, lp["ffn_norm_scale"], eps), precision), lp["ffn_post_norm_scale"])


def _sub(dense_p: Dict, prefix: str) -> Dict:
    return {k[len(prefix):]: v for k, v in dense_p.items() if k.startswith(prefix)}


def stacked(dense_p: Dict, cfg: Dict) -> Dict:
    """The leaves by the program's paths -> the layout `forward` runs on: the
    layers' leaves stacked on a leading layer axis under `STACK` (one compiled
    layer body for all of them). A dict that has the layout already comes
    back as it is."""
    if any(k.startswith(STACK) for k in dense_p):
        return dense_p
    out = {k: v for k, v in dense_p.items() if not k.startswith(WALK)}
    for name in _sub(dense_p, WALK + "layers_0/"):
        out[STACK + name] = jnp.stack([dense_p[f"{WALK}layers_{i}/{name}"]
                                       for i in range(cfg["num_hidden_layers"])])
    return out


def passes_of(cfg: Dict, fault: str = "") -> int:
    return 1 if fault == "one_pass" else cfg["total_ut_steps"]


def forward(dense_p, rows, labels, cfg, precision="f32", fault="", keep_logits=False):
    """rows (B, S, D) the looked-up token rows, labels (B, S) -> (every exit's
    per-token cross-entropy (T, B, S), every pass's gate value (T, B, S), the
    exits' logits (T, B, S, V) where `keep_logits`, else None)."""
    live, eps = stacked(dense_p, cfg), cfg["rms_norm_eps"]
    total = passes_of(cfg, fault)
    layer = jax.checkpoint(lambda x, lp: decoder_layer(lp, x, cfg, precision, fault))

    @jax.checkpoint  # an exit keeps its input alone: its logits are made again in the backward pass
    def exit_of(h, p):
        z = _mm("bsd,dv->bsv", h, p["lm_head"], precision)
        per = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(z, labels[..., None], axis=-1)[..., 0]
        lam = jax.nn.sigmoid(jnp.einsum("bsd,d->bs", h, p["exit_gate_kernel"], precision=HI) + p["exit_gate_bias"][0])
        return per, lam, z

    def walk(h, p, count):
        def one_pass(h, _):
            x = jax.lax.scan(lambda x, lp: (layer(x, lp), None), h, _sub(p, STACK))[0]
            normed = _rms(x, p["norm_f_scale"], eps)
            per, lam, z = exit_of(normed, p)
            return (x if fault == "no_loop_norm" else normed), (per, lam, z if keep_logits else None)

        return jax.lax.scan(one_pass, h, None, length=count)

    if fault == "last_use_only" and total > 1:  # the leaves of every pass but the last carry no gradient
        h, early = walk(rows, jax.tree_util.tree_map(jax.lax.stop_gradient, live), total - 1)
        outs = jax.tree_util.tree_map(lambda a, b: jnp.concatenate([a, b]), early, walk(h, live, 1)[1])
    else:
        outs = walk(rows, live, total)[1]
    return outs


def exit_distribution(lam, fault=""):
    """lam (T, B, S) -> p (T, B, S): p_t = lam_t prod_{s<t} (1 - lam_s) for
    t < T, p_T the rest of the mass."""
    total = lam.shape[0]
    if fault == "flat_exit":
        return jnp.full_like(lam, 1.0 / total)
    if fault == "no_survival":
        return lam
    survive, out = jnp.ones_like(lam[0]), []
    for t in range(total - 1):
        out.append(lam[t] * survive)
        survive = survive * (1.0 - lam[t])
    return jnp.stack(out + [survive])


def loss_of(per_token, lam, weight, cfg, fault=""):
    """-> (loss, [mean H(p), mean p_T, mean l_1, mean l_T]) over the weighted tokens."""
    def mean(x):
        return jnp.sum(x * weight) / jnp.sum(weight)

    p = exit_distribution(lam, fault)
    entropy = -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)
    beta = 0.0 if fault == "no_entropy" else cfg["exit_entropy_weight"]
    per = per_token[-1] if fault == "last_exit_only" else jnp.sum(p * per_token, axis=0) - beta * entropy
    return mean(per), jnp.stack([mean(entropy), mean(p[-1]), mean(per_token[0]), mean(per_token[-1])])


def _adagrad(w, acc, g, cfg):
    acc = acc + g * g
    return w - cfg["learning_rate"] * g / (jnp.sqrt(acc) + cfg["adagrad_epsilon"]), acc


def _store(x, precision):
    """bfloat16 storage of rows and accumulators (`reduce_precision`: a convert
    there and back is a pair the compiler may drop, and on the chip it does)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) if precision == "table_bf16" else x


def train_step(cfg: Dict, precision: str = "f32", fault: str = ""):
    """-> step((dense, their accumulators, rows, theirs), positions (B, S),
    labels, weight) -> (the state after one Adagrad step, (loss, the exits' terms))."""
    def step(state, ix, y, weight):
        dense_p, dacc, rows, accs = state

        def loss_fn(dense_p, pulled):
            per_token, lam, _ = forward(dense_p, pulled["token"], y, cfg, precision, fault)
            return loss_of(per_token, lam, weight, cfg, fault)

        pulled = {n: r[ix] for n, r in rows.items()}
        (loss, terms), (gd, gr) = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(dense_p, pulled)
        new_dense, new_dacc, new_rows, new_accs = {}, {}, {}, {}
        for n in dense_p:
            new_dense[n], new_dacc[n] = _adagrad(dense_p[n], dacc[n], gd[n], cfg)
        for n in rows:
            g = jnp.zeros_like(rows[n]).at[ix].add(gr[n])
            w, a = _adagrad(rows[n], accs[n], g, cfg)
            new_rows[n], new_accs[n] = _store(w, precision), _store(a, precision)
        return (new_dense, new_dacc, new_rows, new_accs), (loss, terms)

    return step


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

    def sums(w0, wk, acck, m_first, m_early):
        g2 = jnp.sum(acck - acc0, axis=-1)
        d2 = jnp.sum(jnp.square(wk - w0), axis=-1)
        return jnp.stack([jnp.sum(g2), jnp.sum(g2 * m_first), jnp.sum(d2), jnp.sum(d2 * m_early)])

    def summary(state, keys, ids, masks):
        dense_k, dacc_k, rows_k, accs_k = state
        groups = leaf_groups(cfg)
        dense: Dict[str, jax.Array] = {}
        for path, shape, init in dense_leaves(cfg):
            if path in dense_k:
                wk, ak = dense_k[path], dacc_k[path]
            else:  # a layer's leaf: its slice of the stack
                layer, _, name = path[len(WALK):].partition("/")
                wk, ak = (t[STACK + name][int(layer.split("_")[1])] for t in (dense_k, dacc_k))
            s = sums(init_leaf(keys, cfg, path, shape, init).reshape(1, -1), wk.reshape(1, -1),
                     ak.reshape(1, -1), 0.0, 0.0)
            dense[groups[path]] = dense.get(groups[path], 0.0) + s
        rows0 = {n: _store(r, precision) for n, r in init_rows(keys, cfg, ids).items()}
        return {"dense": dense,
                "tables": {n: sums(rows0[n], rows_k[n], accs_k[n], masks[1], masks[2]) for n in rows0}}

    return jax.jit(start), jax.jit(train_step(cfg, precision, fault), donate_argnums=0), jax.jit(summary)


def follow(seed: int, cfg: Dict, chips: int, ids: np.ndarray, idx: np.ndarray, labels: np.ndarray,
           masks: np.ndarray, *, precision: str = "f32", fault: str = "") -> Dict:
    """Follow the K stacked steps from the seed. `ids` (N,) the sorted unique
    token ids padded to a fixed N; `idx` (K, B, S) positions into it; `labels`
    (K, B, S); `masks` (3, N) as `reference/deepfm.py` has them.
    -> losses (K,); `exit_terms` (K, 4): mean H(p), mean p_T, mean l_1, mean
    l_T; `pairs_held` empty (no routed layer); per leaf GROUP (`leaf_groups`)
    and per table four sums of squares: the gradients Adagrad received
    (acc_end - acc_start), those on the rows only step 1 touches, the
    parameters' change, and that change on the rows only the first three steps
    touch. One jitted step at a time (the state donated), then one jitted
    summary that makes the start values again, leaf by leaf."""
    del chips  # one program on one chip: nothing is summed across workers
    seq = idx.shape[2]
    weight = np.ones(idx.shape[1:], np.float32)
    if fault == "half_batch":
        weight = weight * (np.arange(seq) < seq // 2)
    keys = make_keys(seed, cfg)
    start, step, summary = _programs(json.dumps(cfg, sort_keys=True), precision, fault)
    state = start(keys, ids)
    per_step = []
    for k in range(idx.shape[0]):
        state, out = step(state, idx[k], labels[k], weight)
        per_step.append(out)
    out = summary(state, keys, ids, masks)
    out["losses"] = jnp.stack([o[0] for o in per_step])
    out["exit_terms"] = jnp.stack([o[1] for o in per_step])  # (K, 4)
    out["pairs_held"] = jnp.zeros((0,))
    return out
