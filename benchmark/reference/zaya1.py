"""Plain ZAYA1 training step (every layer a compressed convolutional attention
sub-layer, then a top-1 routed expert sub-layer behind a router MLP whose
state travels up the stack; the token table tied to the head): float32
`jax.numpy`, matmuls at `highest`, no kernels, no dispatch, no blockwise
softmax; layer 0, then ONE `lax.scan` over the alike layers above it.
Imports nothing of the program and takes nothing the program made: table and
tower come from `benchmark.weights`.

r_0 = E[token]; u = RMSNorm(r) (eps `rms_norm_eps`); layer l:
  merge    r <- s_r (r + b_r) + s_y (y + b_y), y the sub-layer's output, four
           vectors a channel; layer 0's first merge has no (s_r, b_r).
  CCA      q~ = u W_q, k~ = u W_k (H = `num_attention_heads` query heads over
           G = `num_key_value_heads` key/value heads of d = `head_dim`);
           z = [q~ ; k~]; c = Conv_B(Conv_A(z)): Conv_A depthwise causal,
           `cca_time0` taps, bias; Conv_B causal, `cca_time1` taps, bias,
           grouped one group a head (d -> d); m_q[h] = (q~[h] + k~[h // (H/G)])
           / 2, m_k[j] = mean of m_q over group j's heads; q = c_q + m_q, k =
           c_k + m_k; v = [u_t W_v1 ; u_{t-1} W_v2] (head 0 the current
           position's values, head 1 the previous position's, u_{-1} = 0);
           q <- sqrt(d) q / |q|, k <- sqrt(d) exp(tau_j) k / |k| (|x| =
           sqrt(sum x^2 + 1e-6)); rotary half-rotation on the first
           `partial_rotary_factor` d dims of each head, theta `rope_theta`,
           angles made in float64 on the host; softmax(q k^T / sqrt(d) +
           causal mask) v with every key in the softmax (a block of queries
           at a time, one loop over the blocks); W_o.
  router   rho = u W_d + b_d; l > 0: rho <- rho + gamma rho_{l-1} (rho as
           just formed goes on to layer l + 1); logits = W_3 GELU(W_2 GELU(W_1
           RMSNorm(rho) + b_1) + b_2) (GELU by erf); p = softmax(logits);
           e = argmax(p + balance bias); gate = p_e.
  experts  y = gate W_down_e (silu(W_gate_e u) * (W_up_e u)): every HELD
           expert (`num_experts` of the file, offset `expert_offset`, of the
           router's `router_width`) runs on every token, one after another,
           times a dense mask of its gate; absent experts add nothing; no
           shared expert.
logits = RMSNorm(r_L) E^T, E the token table itself; loss = mean softmax
cross-entropy against the next token; dense Adagrad on every leaf, the table
among them: ONE step on the sum of the lookup's and the head's gradients.

`precision`: "f32" the reference; "tower_fp8" feeds every matrix product of
activations float8_e4m3 inputs (the router stays f32); "table_bf16" keeps the
token table and its accumulator in bfloat16; "tower_bf16" (no control: the
configuration's own precision, for calibration) feeds the same products
bfloat16 inputs. `fault`: "half_batch" (the
second half of every sequence weightless), "no_value_shift" (value head 1
reads the current position), "no_qk_mean" (q = c_q, k = c_k), "ungrouped_conv"
(Conv_B's groups each read the sum of ALL heads' channels), "no_router_carry"
(rho_{l-1} never added), "untied_head" (the head a copy of the table's start
values that trains apart: the table keeps the lookup's gradient alone),
"noncausal" (attention without its mask), "router_bf16" (the router's
products, activations and softmax rounded to bfloat16), "softmax_bf16" (the
attention scores rounded to bfloat16 before the softmax), "no_routed" (the
experts' terms left out).
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

FAMILY = "zaya1"
CONTROLS = ("tower_fp8", "table_bf16")
FAULTS = ("half_batch", "no_value_shift", "no_qk_mean", "ungrouped_conv", "no_router_carry", "untied_head",
          "noncausal", "no_routed")
# What the configuration's OWN precision costs, piece by piece: neither controls nor faults on the chip,
# where the program computes in bfloat16 itself and reads as high or higher (PERF.md section 2); against a
# float32 program (the CPU rehearsal) each reads not correct. (kind, name) as `follow` takes them.
CALIBRATIONS = (("precision", "tower_bf16"), ("fault", "router_bf16"), ("fault", "softmax_bf16"))
HI = jax.lax.Precision.HIGHEST
TABLE = "__embeddings__/token"  # the tied table: a dense leaf of the program (`sparse_as_dense`)


def tables_of(cfg: Dict) -> Dict[str, Dict]:
    """No table on the sparse path: the token table trains densely."""
    return {}


def _layer_leaves(cfg: Dict, p: str, first: bool) -> List[Tuple[str, Tuple[int, ...], object]]:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    n, t0, t1 = h + g, cfg["cca_time0"], cfg["cca_time1"]
    a, r, m = p + "cca/", p + "router/", p + "moe/"
    rh, e, f = cfg["router_hidden_size"], cfg["num_experts"], cfg["moe_intermediate_size"]
    out = [(p + "attn_norm_scale", (d,), "ones"),
           (a + "q_proj/kernel", (d, h * hd), d ** -0.5), (a + "k_proj/kernel", (d, g * hd), d ** -0.5),
           (a + "v_proj_now/kernel", (d, g * hd // 2), d ** -0.5),
           (a + "v_proj_prev/kernel", (d, g * hd // 2), d ** -0.5),
           (a + "conv0_kernel", (t0, n * hd), t0 ** -0.5), (a + "conv0_bias", (n * hd,), "zeros"),
           (a + "conv1_kernel", (t1, n, hd, hd), (t1 * hd) ** -0.5), (a + "conv1_bias", (n, hd), "zeros"),
           (a + "key_temp", (g,), "zeros"), (a + "o_proj/kernel", (h * hd, d), (h * hd) ** -0.5)]
    if not first:
        out += [(p + "attn_merge/res_scale", (d,), "ones"), (p + "attn_merge/res_bias", (d,), "zeros")]
    out += [(p + "attn_merge/out_scale", (d,), "ones"), (p + "attn_merge/out_bias", (d,), "zeros"),
            (p + "ffn_norm_scale", (d,), "ones"),
            (r + "down/kernel", (d, rh), d ** -0.5), (r + "down/bias", (rh,), "zeros")]
    if not first:
        out.append((r + "carry_scale", (rh,), "ones"))
    out += [(r + "norm_scale", (rh,), "ones"),
            (r + "fc1/kernel", (rh, rh), rh ** -0.5), (r + "fc1/bias", (rh,), "zeros"),
            (r + "fc2/kernel", (rh, rh), rh ** -0.5), (r + "fc2/bias", (rh,), "zeros"),
            (r + "fc3/kernel", (rh, cfg["router_width"]), rh ** -0.5),
            (r + "balance_bias", (cfg["router_width"],), "zeros"),
            (m + "experts_gate", (e, d, f), d ** -0.5), (m + "experts_up", (e, d, f), d ** -0.5),
            (m + "experts_down", (e, f, d), f ** -0.5)]
    return out + [(p + f"ffn_merge/{k}", (d,), v) for k, v in
                  (("res_scale", "ones"), ("res_bias", "zeros"), ("out_scale", "ones"), ("out_bias", "zeros"))]


def dense_leaves(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], object]]:
    """(path, shape, init) of every leaf the program trains densely, the tied
    token table among them; paths are the program's. `init` is a kernel's
    N(0, init) stddev, or the name of a fixed start."""
    d = cfg["hidden_size"]
    out = [(TABLE, (cfg["vocab_size"], d), cfg["table_init_stddev"])]
    for i in range(cfg["num_hidden_layers"]):
        out += _layer_leaves(cfg, f"layers_{i}/", i == 0)
    return out + [("norm_f_scale", (d,), "ones")]


def leaf_groups(cfg: Dict) -> Dict[str, str]:
    """{leaf path: group}, the map both sides of the comparison sum by: `table`
    (the tied table), `head` (the final norm), a layer's `L<i>.cca` (with its
    norm and merge), `L<i>.router`, `L<i>.experts`, `L<i>.ffn` (the expert
    sub-layer's norm and merge)."""
    out = {}
    for path, _, _ in dense_leaves(cfg):
        if not path.startswith("layers_"):
            out[path] = "table" if path == TABLE else "head"
            continue
        layer, _, rest = path.partition("/")
        part = ("cca" if rest.startswith(("cca/", "attn_")) else "router" if rest.startswith("router/")
                else "experts" if rest.startswith("moe/") else "ffn")
        out[path] = f"L{layer.split('_')[1]}.{part}"
    return out


def group_sizes(cfg: Dict) -> Dict[str, int]:
    """{group: its number of elements}."""
    groups, out = leaf_groups(cfg), {}
    for path, shape, _ in dense_leaves(cfg):
        out[groups[path]] = out.get(groups[path], 0) + int(np.prod(shape))
    return out


def make_keys(seed: int, cfg: Dict) -> Dict[str, np.uint32]:
    return {"dense/" + p: weights.stream_key(seed, "dense/" + p) for p, _, _ in dense_leaves(cfg)}


def init_leaf(keys: Dict, cfg: Dict, path: str, shape, init) -> jax.Array:
    if isinstance(init, str):
        return {"zeros": jnp.zeros, "ones": jnp.ones}[init](shape, jnp.float32)
    return weights.dense_leaf(keys["dense/" + path], shape, init)


def init_dense(keys: Dict, cfg: Dict) -> Dict[str, jax.Array]:
    return {path: init_leaf(keys, cfg, path, shape, init) for path, shape, init in dense_leaves(cfg)}


def init_rows(keys: Dict, cfg: Dict, ids) -> Dict[str, jax.Array]:
    return {}


# -- the model ----------------------------------------------------------------

def _fp8(x):
    q = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


def _bf16(x):
    """Rounded to bfloat16 (`reduce_precision`: a convert there and back is a
    pair the compiler may drop, and on the chip it does), gradient passed on."""
    return x + jax.lax.stop_gradient(jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) - x)


def _mm(spec, a, b, precision):
    if precision == "tower_fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision == "tower_bf16":
        a, b = _bf16(a), _bf16(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _shift(x, by: int):
    """x (B, S, ...) moved `by` positions on: position t reads t - by, the
    positions before the sequence read 0."""
    if not by:
        return x
    return jnp.pad(x, ((0, 0), (by, 0)) + ((0, 0),) * (x.ndim - 2))[:, :x.shape[1]]


def _rotary(x, theta: float, rotary: int):
    """Half-rotation on the first `rotary` dims of each head: x (B, S, H, d);
    the angles pos * theta^(-2i/rotary) as float64 tables made on the host."""
    s, half = x.shape[1], rotary // 2
    ang = np.arange(s, dtype=np.float64)[:, None] * theta ** (-np.arange(half, dtype=np.float64) / half)[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rotary:]], axis=-1)


def cca(p, u, cfg, precision, fault, block=512):
    b, s, _ = u.shape
    h, g, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    n, per = h + g, h // g
    qt = _mm("bsd,de->bse", u, p["q_proj/kernel"], precision)
    kt = _mm("bsd,de->bse", u, p["k_proj/kernel"], precision)
    z = jnp.concatenate([qt, kt], axis=-1)
    t0, t1 = cfg["cca_time0"], cfg["cca_time1"]
    a = p["conv0_bias"] + sum(_shift(z, t0 - 1 - j) * p["conv0_kernel"][j] for j in range(t0))
    a = a.reshape(b, s, n, d)
    if fault == "ungrouped_conv":  # every group reads the sum of all heads' channels
        a = jnp.broadcast_to(jnp.sum(a, axis=2, keepdims=True), a.shape)
    c = p["conv1_bias"] + sum(_mm("bsni,nio->bsno", _shift(a, t1 - 1 - j), p["conv1_kernel"][j], precision)
                              for j in range(t1))
    cq, ck = c[:, :, :h], c[:, :, h:]
    if fault != "no_qk_mean":
        mq = 0.5 * (qt.reshape(b, s, g, per, d) + kt.reshape(b, s, g, 1, d))
        cq, ck = cq + mq.reshape(b, s, h, d), ck + jnp.mean(mq, axis=3)
    v_now = _mm("bsd,de->bse", u, p["v_proj_now/kernel"], precision)
    v_prev = _mm("bsd,de->bse", _shift(u, 0 if fault == "no_value_shift" else 1), p["v_proj_prev/kernel"], precision)
    v = jnp.concatenate([v_now, v_prev], axis=-1).reshape(b, s, g, d)
    rotary = int(d * cfg["partial_rotary_factor"])
    theta = float(cfg["rope_parameters"][cfg["layer_types"][0]]["rope_theta"])
    q = _rotary(_l2(cq) * math.sqrt(d), theta, rotary)
    k = _rotary(_l2(ck) * (math.sqrt(d) * jnp.exp(p["key_temp"]))[:, None], theta, rotary)
    k, v = (jnp.repeat(t, per, axis=2) for t in (k, v))

    @jax.checkpoint
    def rows(qb, lo):
        sc = _mm("bqhd,bkhd->bhqk", qb, k, precision) / math.sqrt(d)
        if fault == "softmax_bf16":
            sc = _bf16(sc)
        if fault != "noncausal":
            sc = jnp.where((lo + jnp.arange(qb.shape[1]))[:, None] >= jnp.arange(s)[None, :], sc, -jnp.inf)
        return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v, precision)

    block = min(block, s)
    whole = s // block * block  # the blocks of equal size as one loop, what is left as a last block
    o = jax.lax.map(lambda x: rows(*x), (q[:, :whole].reshape(b, -1, block, h, d).swapaxes(0, 1),
                                         jnp.arange(0, whole, block)))
    o = o.swapaxes(0, 1).reshape(b, whole, h, d)
    if whole < s:
        o = jnp.concatenate([o, rows(q[:, whole:], whole)], axis=1)
    return _mm("bse,ed->bsd", o.reshape(b, s, h * d), p["o_proj/kernel"], precision)


def route(p, ut, prev, cfg, fault):
    """ut (T, D), prev (T, R) or None -> (chosen (T,), gate (T,), rho (T, R))."""
    low = _bf16 if fault == "router_bf16" else (lambda x: x)

    def dense(name, x, bias=True):
        y = jnp.matmul(low(x), low(p[name + "/kernel"]), precision=HI)
        return low(y + p[name + "/bias"] if bias else y)

    rho = dense("down", ut)
    if prev is not None and fault != "no_router_carry":
        rho = rho + p["carry_scale"] * prev
    x = low(_rms(rho, p["norm_scale"], cfg["rms_norm_eps"]))
    x = low(_gelu(dense("fc1", x)))
    x = low(_gelu(dense("fc2", x)))
    prob = low(jax.nn.softmax(dense("fc3", x, bias=False), axis=-1))
    chosen = jnp.argmax(prob + jax.lax.stop_gradient(p["balance_bias"]), axis=-1)
    return chosen, jnp.take_along_axis(prob, chosen[:, None], axis=-1)[:, 0], rho


def experts(p, ut, chosen, gate, cfg, precision):
    """The terms of the experts held: [expert_offset, expert_offset +
    num_experts) of `router_width`; (T, D)."""
    first, count = cfg["expert_offset"], cfg["num_experts"]

    @jax.checkpoint  # keeps the expert's weights alone; its hidden states are made again
    def term(held):  # a dense mask: this expert's gate for every token (0 where not chosen)
        e, w_gate, w_up, w_down = held
        w = jnp.where(chosen == first + e, gate, 0.0)
        gx = _mm("td,df->tf", ut, w_gate, precision)
        hid = gx * jax.nn.sigmoid(gx) * _mm("td,df->tf", ut, w_up, precision)
        return w[:, None] * _mm("tf,fd->td", hid, w_down, precision)

    def add(out, held):
        return out + term(held), None

    return jax.lax.scan(add, jnp.zeros_like(ut), (jnp.arange(count), p["experts_gate"], p["experts_up"],
                                                  p["experts_down"]))[0]


def _sub(dense_p: Dict, prefix: str) -> Dict:
    return {k[len(prefix):]: v for k, v in dense_p.items() if k.startswith(prefix)}


def _merge(p, r, y, scale_residual=True):
    if scale_residual:
        r = p["res_scale"] * (r + p["res_bias"])
    return r + p["out_scale"] * (y + p["out_bias"])


def decoder_layer(lp, r, carried, cfg, first, precision, fault):
    """-> (r, this layer's router state, the pairs routed to held experts)."""
    eps = cfg["rms_norm_eps"]
    b, s, d = r.shape
    u = _rms(r, lp["attn_norm_scale"], eps)
    r = _merge(_sub(lp, "attn_merge/"), r, cca(_sub(lp, "cca/"), u, cfg, precision, fault), not first)
    u = _rms(r, lp["ffn_norm_scale"], eps)
    ut = u.reshape(b * s, d)
    chosen, gate, rho = route(_sub(lp, "router/"), ut, None if first else carried, cfg, fault)
    lo = cfg["expert_offset"]
    sent = jnp.sum((chosen >= lo) & (chosen < lo + cfg["num_experts"]))
    y = jnp.zeros_like(ut) if fault == "no_routed" else experts(_sub(lp, "moe/"), ut, chosen, gate, cfg, precision)
    return _merge(_sub(lp, "ffn_merge/"), r, y.reshape(b, s, d)), rho, sent


STACK = "layers_1+/"  # layers 1 and up are alike: their leaves stacked on a leading axis, one `lax.scan` over them


def stacked(dense_p: Dict, cfg: Dict) -> Dict:
    """The leaves by the program's paths -> the layout `forward` runs on: layer
    0's and the stack's ends as they are, the leaves of layers 1.. stacked on a
    leading layer axis under `STACK` (one compiled layer body for all of them:
    a sixth of the code and of the compile). A dict that has the layout
    already comes back as it is."""
    layers = cfg["num_hidden_layers"]
    if layers < 2 or any(k.startswith(STACK) for k in dense_p):
        return dense_p
    out = {k: v for k, v in dense_p.items() if not k.startswith("layers_") or k.startswith("layers_0/")}
    for name in _sub(dense_p, "layers_1/"):
        out[STACK + name] = jnp.stack([dense_p[f"layers_{i}/{name}"] for i in range(1, layers)])
    return out


def forward(dense_p, tokens, cfg, precision="f32", fault=""):
    """tokens (B, S) ids -> (logits (B, S, V), the pairs routed to held experts
    in each layer). The head is the table the rows come from (`fault`
    "untied_head": the leaf `__head__` instead)."""
    p = stacked(dense_p, cfg)
    table = p[TABLE]

    def layer(first):
        return jax.checkpoint(lambda r, carried, lp: decoder_layer(lp, r, carried, cfg, first, precision, fault))

    carried = jnp.zeros((tokens.size, cfg["router_hidden_size"]), jnp.float32)
    r, carried, sent = layer(True)(table[tokens], carried, _sub(p, "layers_0/"))
    pairs = sent[None]
    if cfg["num_hidden_layers"] > 1:
        def body(state, lp):
            r, carried, sent = layer(False)(*state, lp)
            return (r, carried), sent

        (r, carried), rest = jax.lax.scan(body, (r, carried), _sub(p, STACK))
        pairs = jnp.concatenate([pairs, rest])
    head = p["__head__"] if fault == "untied_head" else table
    logits = _mm("bsd,vd->bsv", _rms(r, p["norm_f_scale"], cfg["rms_norm_eps"]), head, precision)
    return logits, pairs


def logits_fn(dense_p, tokens, cfg, precision="f32", fault=""):
    return forward(dense_p, tokens, cfg, precision, fault)[0]


def xent(logits, labels, weight):
    per = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(per * weight) / jnp.sum(weight)


def _adagrad(w, acc, g, cfg):
    acc = acc + g * g
    return w - cfg["learning_rate"] * g / (jnp.sqrt(acc) + cfg["adagrad_epsilon"]), acc


def _store(x, precision):
    """bfloat16 storage of the table and its accumulator."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7) if precision == "table_bf16" else x


def train_step(cfg: Dict, precision: str = "f32", fault: str = ""):
    """-> step((dense, their accumulators), tokens (B, S), labels, weight) ->
    (the state after one step of dense Adagrad, (loss, pairs held a layer))."""
    def step(state, tokens, y, weight):
        dense_p, dacc = state

        def loss_fn(dense_p):
            logits, pairs = forward(dense_p, tokens, cfg, precision, fault)
            return xent(logits, y, weight), pairs

        (loss, pairs), gd = jax.value_and_grad(loss_fn, has_aux=True)(dense_p)
        new_dense, new_dacc = {}, {}
        for n in dense_p:
            w, a = _adagrad(dense_p[n], dacc[n], gd[n], cfg)
            new_dense[n], new_dacc[n] = (_store(w, precision), _store(a, precision)) if n == TABLE else (w, a)
        return (new_dense, new_dacc), (loss, pairs)

    return step


@functools.lru_cache(maxsize=2)
def _programs(cfg_json: str, precision: str, fault: str):
    """The jitted start, step and summary of one (configuration, precision,
    fault), kept for the next call, so that a checker's seeds share a compile;
    two at a time (the reference and one control or fault: at the cell's size
    a step is 150 MB of code on the device beside 11.9 GiB of state and
    temporaries)."""
    cfg = json.loads(cfg_json)
    acc0 = cfg["adagrad_initial_accumulator"]

    def start(keys):
        dense = stacked(init_dense(keys, cfg), cfg)
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
            w0 = init_leaf(keys, cfg, path, shape, init)
            a0 = jnp.float32(acc0)
            if path == TABLE:
                w0, a0 = _store(w0, precision), _store(a0, precision)
            layer, _, name = path.partition("/")
            if path in dense_k:
                wk, ak = dense_k[path], dacc_k[path]
            else:  # a leaf of layers 1..: its slice of the stack
                wk, ak = (t[STACK + name][int(layer.split("_")[1]) - 1] for t in (dense_k, dacc_k))
            sums = jnp.stack([jnp.sum(ak - a0), 0.0, jnp.sum(jnp.square(wk - w0)), 0.0])
            dense[groups[path]] = dense.get(groups[path], 0.0) + sums
        return {"dense": dense, "tables": {}}

    return jax.jit(start), jax.jit(train_step(cfg, precision, fault), donate_argnums=0), jax.jit(summary)


def follow(seed: int, cfg: Dict, chips: int, ids: np.ndarray, idx: np.ndarray, labels: np.ndarray,
           masks: np.ndarray, *, precision: str = "f32", fault: str = "") -> Dict:
    """Follow the K stacked steps from the seed. `ids` (N,) the sorted unique
    token ids padded to a fixed N; `idx` (K, B, S) positions into it; `labels`
    (K, B, S); `masks` unused (no table is on the sparse path: the rows only
    some steps touch are not told apart). -> losses (K,); `pairs_held` (K,
    layers); per leaf GROUP (`leaf_groups`) two sums of squares in the
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
    per_step = []
    for k in range(idx.shape[0]):
        state, out = step(state, tokens[k], labels[k], weight)
        per_step.append(out)
    out = summary(state, keys)
    out["losses"] = jnp.stack([o[0] for o in per_step])
    out["pairs_held"] = jnp.stack([o[1] for o in per_step])  # (K, layers)
    return out
