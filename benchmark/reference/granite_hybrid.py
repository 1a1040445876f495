"""Plain Granite 4.0-H training step on PACKED sequences (nine Mamba-2 layers
in ten, the tenth grouped-query attention without positions, a SwiGLU after
every mixer, four muP scalars, the token table tied to the head): float32
`jax.numpy`, matmuls at `highest`, no kernels, no chunked scan, no blockwise
softmax. Imports nothing of the program and takes nothing the program made:
table and tower come from `benchmark.weights`.

Tokens x_0..x_{S-1}; start_t in {0, 1}, start_0 = 1; n_t = sum_{u<=t} start_u,
the document of position t.
  r = `embedding_multiplier` E[x]; layer i (eps `rms_norm_eps`):
  r <- r + `residual_multiplier` Mixer_i(RMSNorm(r)), then
  r <- r + `residual_multiplier` W_out(silu(a) * b), [a ; b] = W_in RMSNorm(r).
  mamba      [z ; xBC ; dt] = W_in u; xBC <- silu(conv(xBC) + b): depthwise,
             `mamba_d_conv` taps ONE AT A TIME, the tap that reads position
             u < t counting only where n_u = n_t; [x ; B ; C], B and C in
             `mamba_n_groups` groups; dt <- softplus(dt + dt_bias), A =
             -exp(A_log); h_t = (start_t ? 0 : exp(dt_t A) h_{t-1}) + dt_t B_t
             (x) x_t, y_t = C_t . h_t + D x_t, ONE POSITION AT A TIME
             (`lax.scan` in rematerialised blocks, the state zeroed by
             `where(start_t, ...)`); y <- RMSNorm(y * silu(z)) over each
             group's channels; W_out y.
  attention  q, k, v heads of hidden_size / num_attention_heads, no bias, no
             positions; softmax over the keys k <= q with n_k = n_q of
             `attention_multiplier` q.k, every key in the softmax (a block of
             queries at a time, one loop over the blocks); W_o.
logits = RMSNorm(r_L) E^T / `logits_scaling`, E the token table itself; loss =
mean softmax cross-entropy against the next token over EVERY position (no mask
at document ends); dense Adagrad on every leaf, the table among them: ONE step
on the sum of the lookup's and the head's gradients.

`precision`: "f32" the reference; "tower_fp8" feeds every matrix product of
activations float8_e4m3 inputs; "table_bf16" keeps the token table and its
accumulator in bfloat16. `fault`: "half_batch" (the second half of every
sequence weightless), "no_state_reset" (the state runs on through every
start), "conv_leak" (the taps read the document before), "no_segment_mask"
(a query sees every earlier key of the sequence), "noncausal" (a query sees
its whole document, later keys too), "attn_scale_rsqrt" (head_dim^-1/2 for
`attention_multiplier`), "no_residual_multiplier", "no_embedding_multiplier",
"no_logits_scaling" (each scalar 1), "untied_head" (the head a copy of the
table's start values that trains apart: the table keeps the lookup's gradient
alone).
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

FAMILY = "granite_hybrid"
CONTROLS = ("tower_fp8", "table_bf16")
FAULTS = ("half_batch", "no_state_reset", "conv_leak", "no_segment_mask", "noncausal", "attn_scale_rsqrt",
          "no_residual_multiplier", "no_embedding_multiplier", "no_logits_scaling", "untied_head")
HI = jax.lax.Precision.HIGHEST
TABLE = "__embeddings__/token"  # the tied table: a dense leaf of the program (`sparse_as_dense`)
SCAN_BLOCK = 64                 # positions of the recurrence rematerialised together
QUERY_BLOCK = 512               # queries whose scores against every key are alive together


def tables_of(cfg: Dict) -> Dict[str, Dict]:
    """No table on the sparse path: the token table trains densely."""
    return {}


def kinds_of(cfg: Dict) -> List[str]:
    """The layers held: the first `num_hidden_layers` of the published list."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def head_dim_of(cfg: Dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _mamba_dims(cfg: Dict) -> Tuple[int, int, int]:
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    bc = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return inner, bc, inner + 2 * bc


def _layer_leaves(cfg: Dict, p: str, kind: str) -> List[Tuple[str, Tuple[int, ...], object]]:
    d, f, m = cfg["hidden_size"], cfg["shared_intermediate_size"], p + "mixer/"
    out = [(p + "mixer_norm_scale", (d,), "ones")]
    if kind == "mamba":
        inner, _, conv_dim = _mamba_dims(cfg)
        h, k = cfg["mamba_n_heads"], cfg["mamba_d_conv"]
        out += [(m + "in_proj/kernel", (d, inner + conv_dim + h), d ** -0.5),
                (m + "conv_kernel", (k, conv_dim), k ** -0.5), (m + "conv_bias", (conv_dim,), "zeros"),
                (m + "dt_bias", (h,), "dt_bias"), (m + "A_log", (h,), "a_log"), (m + "D", (h,), "ones"),
                (m + "norm_scale", (inner,), "ones"), (m + "out_proj/kernel", (inner, d), inner ** -0.5)]
    else:
        q = cfg["num_attention_heads"] * head_dim_of(cfg)
        kv = cfg["num_key_value_heads"] * head_dim_of(cfg)
        out += [(m + "q_proj/kernel", (d, q), d ** -0.5), (m + "k_proj/kernel", (d, kv), d ** -0.5),
                (m + "v_proj/kernel", (d, kv), d ** -0.5), (m + "o_proj/kernel", (q, d), q ** -0.5)]
    return out + [(p + "mlp_norm_scale", (d,), "ones"), (p + "mlp_in", (d, 2 * f), d ** -0.5),
                  (p + "mlp_out", (f, d), f ** -0.5)]


def dense_leaves(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], object]]:
    """(path, shape, init) of every leaf the program trains densely, the tied
    token table among them; paths are the program's. `init` is a kernel's
    N(0, init) stddev, or the name of a fixed start."""
    out = [(TABLE, (cfg["vocab_size"], cfg["hidden_size"]), cfg["table_init_stddev"])]
    for i, kind in enumerate(kinds_of(cfg)):
        out += _layer_leaves(cfg, f"layers_{i}/", kind)
    return out + [("norm_f_scale", (cfg["hidden_size"],), "ones")]


def leaf_groups(cfg: Dict) -> Dict[str, str]:
    """{leaf path: group}, the map both sides of the comparison sum by: `table`
    (the tied table), `norm_f`, a layer's `L<i>.M` or `L<i>.attn` (the mixer
    with its norm) and `L<i>.mlp` (the SwiGLU with its norm)."""
    kinds, out = kinds_of(cfg), {}
    for path, _, _ in dense_leaves(cfg):
        if not path.startswith("layers_"):
            out[path] = "table" if path == TABLE else "norm_f"
            continue
        layer, _, rest = path.partition("/")
        i = int(layer.split("_")[1])
        out[path] = f"L{i}." + ("mlp" if rest.startswith("mlp_") else "M" if kinds[i] == "mamba" else "attn")
    return out


def group_sizes(cfg: Dict) -> Dict[str, int]:
    """{group: its number of elements}."""
    groups, out = leaf_groups(cfg), {}
    for path, shape, _ in dense_leaves(cfg):
        out[groups[path]] = out.get(groups[path], 0) + int(np.prod(shape))
    return out


def make_keys(seed: int, cfg: Dict) -> Dict[str, np.uint32]:
    return {"dense/" + p: weights.stream_key(seed, "dense/" + p) for p, _, _ in dense_leaves(cfg)}


def _fixed(kind: str, shape, cfg: Dict):
    n = shape[0]
    if kind in ("zeros", "ones"):
        return {"zeros": jnp.zeros, "ones": jnp.ones}[kind](shape, jnp.float32)
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
    return {}


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


def documents(starts):
    """starts (B, S) -> n (B, S): the document of each position."""
    return jnp.cumsum((starts != 0).astype(jnp.int32), axis=1)


def conv(x, w, bias, n):
    """Depthwise causal convolution tap by tap: x (B, S, C), w (K, C); the tap
    that reads `back` positions before t counts where that position exists and
    is of t's document (`n` None: wherever it exists)."""
    taps, s = w.shape[0], x.shape[1]
    out = bias + x * w[taps - 1]
    for back in range(1, taps):
        read = jnp.pad(x, ((0, 0), (back, 0), (0, 0)))[:, :s]
        if n is not None:
            same = jnp.pad(n, ((0, 0), (back, 0)), constant_values=-1)[:, :s] == n
            read = jnp.where(same[..., None], read, 0.0)
        out = out + read * w[taps - 1 - back]
    return out


def recurrence(x, dt, A, B, C, starts):
    """h_t = (start_t ? 0 : exp(dt_t A) h_{t-1}) + dt_t B_t (x) x_t; y_t = C_t .
    h_t, one position at a time. x (Bt, L, H, P); dt (Bt, L, H); A (H,); B, C
    (Bt, L, G, N); starts (Bt, L) bool."""
    Bt, L, H, P = x.shape
    G, N = B.shape[2:]
    r = H // G
    pad = (-L) % SCAN_BLOCK
    feed = [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (x, dt, B, C, starts)]
    blocks = (L + pad) // SCAN_BLOCK
    feed = tuple(jnp.moveaxis(t, 1, 0).reshape((blocks, SCAN_BLOCK) + t.shape[:1] + t.shape[2:]) for t in feed)

    def step(h, f):
        xt, dtt, bt, ct, st = f
        h = jnp.where(st[:, None, None, None], 0.0, h * jnp.exp(dtt * A)[..., None, None])
        bh = jnp.repeat(bt, r, axis=1)                       # (Bt, H, N)
        ch = jnp.repeat(ct, r, axis=1)
        h = h + (dtt[..., None] * xt)[..., None] * bh[:, :, None, :]
        return h, jnp.sum(h * ch[:, :, None, :], axis=-1)

    @jax.checkpoint
    def block(h, f):
        return jax.lax.scan(step, h, f)

    _, y = jax.lax.scan(block, jnp.zeros((Bt, H, P, N), jnp.float32), feed)
    return jnp.moveaxis(y.reshape((L + pad, Bt, H, P)), 0, 1)[:, :L]


def mamba(p, u, starts, cfg, precision, fault):
    inner, bc, conv_dim = _mamba_dims(cfg)
    h, hd, g, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_n_groups"], cfg["mamba_d_state"]
    zxbcdt = _mm("bsd,de->bse", u, p["in_proj/kernel"], precision)
    z, xbc, dt = jnp.split(zxbcdt, [inner, inner + conv_dim], axis=-1)
    xbc = _silu(conv(xbc, p["conv_kernel"], p["conv_bias"], None if fault == "conv_leak" else documents(starts)))
    xs, bm, cm = jnp.split(xbc, [inner, inner + bc], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    xh = xs.reshape(xs.shape[:2] + (h, hd))
    reset = jnp.zeros_like(starts, bool) if fault == "no_state_reset" else starts != 0
    y = recurrence(xh, dt, -jnp.exp(p["A_log"]), bm.reshape(bm.shape[:2] + (g, n)),
                   cm.reshape(cm.shape[:2] + (g, n)), reset)
    y = (y + xh * p["D"][:, None]).reshape(xs.shape)
    y = _rms(y * _silu(z), p["norm_scale"], cfg["rms_norm_eps"], groups=g)
    return _mm("bse,ed->bsd", y, p["out_proj/kernel"], precision)


def attention(p, u, starts, cfg, precision, fault):
    b, s, _ = u.shape
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], head_dim_of(cfg)
    q = _mm("bsd,de->bse", u, p["q_proj/kernel"], precision).reshape(b, s, hq, d)
    k = _mm("bsd,de->bse", u, p["k_proj/kernel"], precision).reshape(b, s, hkv, d)
    v = _mm("bsd,de->bse", u, p["v_proj/kernel"], precision).reshape(b, s, hkv, d)
    k, v = (jnp.repeat(t, hq // hkv, axis=2) for t in (k, v))
    scale = d ** -0.5 if fault == "attn_scale_rsqrt" else cfg["attention_multiplier"]
    n = documents(starts)

    @jax.checkpoint
    def rows(qb, nq, lo):
        sc = _mm("bqhd,bkhd->bhqk", qb, k, precision) * scale
        seen = jnp.ones((b, qb.shape[1], s), bool)
        if fault != "noncausal":
            seen = seen & ((lo + jnp.arange(qb.shape[1]))[:, None] >= jnp.arange(s)[None, :])
        if fault != "no_segment_mask":
            seen = seen & (nq[:, :, None] == n[:, None, :])
        sc = jnp.where(seen[:, None], sc, -jnp.inf)
        return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v, precision)

    block = min(QUERY_BLOCK, s)
    whole = s // block * block  # the blocks of equal size as one loop, what is left as a last block
    o = jax.lax.map(lambda x: rows(*x), (q[:, :whole].reshape(b, -1, block, hq, d).swapaxes(0, 1),
                                         n[:, :whole].reshape(b, -1, block).swapaxes(0, 1),
                                         jnp.arange(0, whole, block)))
    o = o.swapaxes(0, 1).reshape(b, whole, hq, d)
    if whole < s:
        o = jnp.concatenate([o, rows(q[:, whole:], n[:, whole:], whole)], axis=1)
    return _mm("bse,ed->bsd", o.reshape(b, s, hq * d), p["o_proj/kernel"], precision)


def _sub(dense_p: Dict, prefix: str) -> Dict:
    return {k[len(prefix):]: v for k, v in dense_p.items() if k.startswith(prefix)}


def decoder_layer(lp, r, starts, cfg, kind, precision, fault):
    eps = cfg["rms_norm_eps"]
    by = 1.0 if fault == "no_residual_multiplier" else cfg["residual_multiplier"]
    mixer = mamba if kind == "mamba" else attention
    r = r + by * mixer(_sub(lp, "mixer/"), _rms(r, lp["mixer_norm_scale"], eps), starts, cfg, precision, fault)
    ab = _mm("bsd,df->bsf", _rms(r, lp["mlp_norm_scale"], eps), lp["mlp_in"], precision)
    a, b = jnp.split(ab, 2, axis=-1)
    return r + by * _mm("bsf,fd->bsd", _silu(a) * b, lp["mlp_out"], precision)


def forward(dense_p, tokens, starts, cfg, precision="f32", fault=""):
    """tokens (B, S) ids, starts (B, S) -> logits (B, S, V). The head is the
    table the rows come from (`fault` "untied_head": the leaf `__head__`)."""
    table = dense_p[TABLE]
    r = table[tokens] * (1.0 if fault == "no_embedding_multiplier" else cfg["embedding_multiplier"])
    for i, kind in enumerate(kinds_of(cfg)):
        layer = jax.checkpoint(functools.partial(decoder_layer, cfg=cfg, kind=kind, precision=precision, fault=fault))
        r = layer(_sub(dense_p, f"layers_{i}/"), r, starts)
    head = dense_p["__head__"] if fault == "untied_head" else table
    logits = _mm("bsd,vd->bsv", _rms(r, dense_p["norm_f_scale"], cfg["rms_norm_eps"]), head, precision)
    return logits / (1.0 if fault == "no_logits_scaling" else cfg["logits_scaling"])


def xent(logits, labels, weight):
    per = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(per * weight) / jnp.sum(weight)


def _adagrad(w, acc, g, cfg):
    acc = acc + g * g
    return w - cfg["learning_rate"] * g / (jnp.sqrt(acc) + cfg["adagrad_epsilon"]), acc


def _store(x, precision):
    """bfloat16 storage of the table and its accumulator (`reduce_precision`:
    a convert there and back is a pair the compiler may drop)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) if precision == "table_bf16" else x


def train_step(cfg: Dict, precision: str = "f32", fault: str = ""):
    """-> step((dense, their accumulators), tokens (B, S), starts, labels,
    weight) -> (the state after one step of dense Adagrad, the loss)."""
    def step(state, tokens, starts, y, weight):
        dense_p, dacc = state
        loss, gd = jax.value_and_grad(
            lambda p: xent(forward(p, tokens, starts, cfg, precision, fault), y, weight))(dense_p)
        new_dense, new_dacc = {}, {}
        for n in dense_p:
            w, a = _adagrad(dense_p[n], dacc[n], gd[n], cfg)
            new_dense[n], new_dacc[n] = (_store(w, precision), _store(a, precision)) if n == TABLE else (w, a)
        return (new_dense, new_dacc), loss

    return step


@functools.lru_cache(maxsize=2)
def _programs(cfg_json: str, precision: str, fault: str):
    """The jitted start, step and summary of one (configuration, precision,
    fault), kept for the next call, so that a checker's seeds share a compile;
    two at a time (the reference and one control or fault)."""
    cfg = json.loads(cfg_json)
    acc0 = cfg["adagrad_initial_accumulator"]

    def start(keys):
        dense = init_dense(keys, cfg)
        dense[TABLE] = _store(dense[TABLE], precision)
        if fault == "untied_head":
            dense["__head__"] = dense[TABLE]
        acc = {n: jnp.full_like(p, acc0) for n, p in dense.items()}
        acc[TABLE] = _store(acc[TABLE], precision)
        return dense, acc

    def summary(state, keys):
        dense_k, dacc_k = state
        groups = leaf_groups(cfg)
        dense: Dict[str, jax.Array] = {}
        for path, shape, init in dense_leaves(cfg):
            w0, a0 = init_leaf(keys, cfg, path, shape, init), jnp.float32(acc0)
            if path == TABLE:
                w0, a0 = _store(w0, precision), _store(a0, precision)
            sums = jnp.stack([jnp.sum(dacc_k[path] - a0), 0.0, jnp.sum(jnp.square(dense_k[path] - w0)), 0.0])
            dense[groups[path]] = dense.get(groups[path], 0.0) + sums
        return {"dense": dense, "tables": {}}

    return jax.jit(start), jax.jit(train_step(cfg, precision, fault), donate_argnums=0), jax.jit(summary)


def follow(seed: int, cfg: Dict, chips: int, ids: np.ndarray, idx: np.ndarray, labels: np.ndarray,
           masks: np.ndarray, *, starts: np.ndarray, precision: str = "f32", fault: str = "") -> Dict:
    """Follow the K stacked steps from the seed. `ids` (N,) the sorted unique
    token ids padded to a fixed N; `idx` (K, B, S) positions into it; `labels`
    and `starts` (K, B, S); `masks` unused (no table is on the sparse path).
    -> losses (K,); per leaf GROUP (`leaf_groups`) two sums of squares in the
    layout of `reference/deepfm.py`'s four: the gradients Adagrad received
    (acc_end - acc_start) and the parameters' change. One jitted step at a
    time (the state donated), then one jitted summary that makes the start
    values again, leaf by leaf."""
    del chips, masks  # one program on one chip: nothing is summed across workers
    tokens = np.asarray(ids)[np.asarray(idx)]
    seq = idx.shape[2]
    weight = np.ones(idx.shape[1:], np.float32)
    if fault == "half_batch":
        weight = weight * (np.arange(seq) < seq // 2)
    keys = make_keys(seed, cfg)
    start, step, summary = _programs(json.dumps(cfg, sort_keys=True), precision, fault)
    state = start(keys)
    losses = []
    for k in range(idx.shape[0]):
        state, loss = step(state, tokens[k], np.asarray(starts[k]), labels[k], weight)
        losses.append(loss)
    out = summary(state, keys)
    out["losses"] = jnp.stack(losses)
    return out
