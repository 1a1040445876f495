"""Plain Solar-Open2 training step (a hybrid decoder: one gated softmax layer
without positions, then three gated delta-rule linear-attention layers, every
layer routed): float32 `jax.numpy`, matmuls at `highest`, no kernels, no
packing, no chunked form, no blockwise softmax, no dispatch. Imports nothing
of the program and takes nothing the program made: rows and tower come from
`benchmark.weights`.

x_0 = table[token]; layer l: x += Mix_l(RMSNorm(x)); x += MoE(RMSNorm(x)); no
bias anywhere, eps `rms_norm_eps`. Mix_l is the softmax layer where l is in
`gqa_layers`, else the linear layer.
  softmax  q, k, v = W_q x, W_k x, W_v x (`num_attention_heads` query heads
           over `num_key_value_heads` key/value heads of `head_dim`); NO
           positions; softmax(q k^T / sqrt(d) + causal mask) v with every key
           in the softmax (a block of queries at a time, one loop over the
           blocks); W_o [sigmoid(W_g x) * o].
  linear   a head h of width d (`linear_attn_config`): q = L2Norm(SiLU(Conv(W_q
           x))) / sqrt(d), k = L2Norm(SiLU(Conv(W_k x))), v = SiLU(Conv(W_v x)),
           Conv a depthwise causal convolution of `short_conv_kernel_size` taps
           without bias, L2Norm(x) = x / sqrt(sum x^2 + 1e-6);
           g = -exp(A_log_h) softplus(W_f2 W_f1 x + dt_bias), a CHANNEL;
           beta = 2 sigmoid(w_b,h . x) (`kda_allow_neg_eigval`);
           S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,
           S_0 = 0; o_t = S_t^T q_t -- the recurrence ONE POSITION AT A TIME, a
           `lax.scan` over t (in rematerialised blocks of positions, so that its
           backward pass fits); W_o [sigmoid(W_g2 W_g1 x) * RMSNorm_head(o)].
  MoE      s = sigmoid(x W_r) in f32; the top k of s + correction bias;
           weights = chosen s / their sum * routed_scaling_factor; every HELD
           expert (`n_routed_experts` of the file, offset `expert_offset`, of
           the router's `router_width`) runs on every token, one after another,
           times a dense mask of its weight; absent experts add nothing; plus
           the shared expert; all SwiGLU.
logits = RMSNorm(x) W_head; loss = mean softmax cross-entropy against the next
token; dense Adagrad on every leaf and on the touched rows, duplicates summed.

The head counts of the file are the heads HELD (a tensor-parallel rank's):
a sub-layer's output is the held heads' part of its output projection's sum.

Departures from the published description (each a line under `assumed` in the
configuration): the rank of the two low-rank gates (`gate_rank`), the start
values of `A_log` and `dt_bias`, the router's scoring function, the softmax
layer's gate as sigmoid(W_g x) and no q / k norm there.

`precision`: "f32" the reference; "tower_fp8" feeds every matrix product of
activations float8_e4m3 inputs (the router, the decays and the recurrence stay
f32); "table_bf16" keeps rows and their accumulators in bfloat16. `fault`:
"half_batch" (the second half of every sequence weightless), "no_routed" (the
routed experts' terms left out), "drop_eighth" (every eighth token dropped at
dispatch), "noncausal" (softmax attention without its mask), "chunk_reset"
(the linear layers' state zeroed at every chunk boundary), "no_decay" (alpha =
1), "no_delta" (the -beta k k^T factor dropped: S_t = alpha S_{t-1} + beta k
v^T), "beta_unscaled" (beta in (0, 1)), "no_gate" (both output gates = 1).
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

FAMILY = "solar_open2"
CONTROLS = ("tower_fp8", "table_bf16")
FAULTS = ("half_batch", "no_routed", "drop_eighth", "noncausal", "chunk_reset", "no_decay", "no_delta",
          "beta_unscaled", "no_gate")
HI = jax.lax.Precision.HIGHEST
SCAN_BLOCK = 64  # positions of the recurrence rematerialised together


def tables_of(cfg: Dict) -> Dict[str, Dict]:
    return {"token": {"width": cfg["hidden_size"], "zero_cols": 0}}


def is_softmax(cfg: Dict, layer: int) -> bool:
    return layer in cfg["gqa_layers"]


def _layer_leaves(cfg: Dict, p: str, softmax: bool) -> List[Tuple[str, Tuple[int, ...], object]]:
    d = cfg["hidden_size"]
    out = [(p + "mix_norm_scale", (d,), "ones")]
    if softmax:
        a, hd = p + "attn/", cfg["head_dim"]
        hq, hkv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
        out += [(a + "q_proj/kernel", (d, hq), d ** -0.5), (a + "k_proj/kernel", (d, hkv), d ** -0.5),
                (a + "v_proj/kernel", (d, hkv), d ** -0.5)]
        if cfg["use_gqa_gate"]:
            out.append((a + "g_proj/kernel", (d, hq), d ** -0.5))
        out.append((a + "o_proj/kernel", (hq, d), hq ** -0.5))
    else:
        a, lin, r = p + "kda/", cfg["linear_attn_config"], cfg["gate_rank"]
        h, hd, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
        inner = h * hd
        out += [(a + n + "/kernel", (d, inner), d ** -0.5) for n in ("q_proj", "k_proj", "v_proj")]
        out += [(a + "conv_kernel", (taps, 3 * inner), taps ** -0.5),
                (a + "f_a/kernel", (d, r), d ** -0.5), (a + "f_b/kernel", (r, inner), r ** -0.5),
                (a + "dt_bias", (inner,), "dt_bias"), (a + "A_log", (h,), "A_log"),
                (a + "b_proj/kernel", (d, h), d ** -0.5),
                (a + "g_a/kernel", (d, r), d ** -0.5), (a + "g_b/kernel", (r, inner), r ** -0.5),
                (a + "o_norm_scale", (hd,), "ones"), (a + "o_proj/kernel", (inner, d), inner ** -0.5)]
    m = p + "moe/"
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    s = cfg["n_shared_experts"] * f
    return out + [(p + "ffn_norm_scale", (d,), "ones"),
                  (m + "router_kernel", (d, cfg["router_width"]), d ** -0.5),
                  (m + "router_correction_bias", (cfg["router_width"],), "zeros"),
                  (m + "experts_gate", (e, d, f), d ** -0.5), (m + "experts_up", (e, d, f), d ** -0.5),
                  (m + "experts_down", (e, f, d), f ** -0.5),
                  (m + "shared_gate", (d, s), d ** -0.5), (m + "shared_up", (d, s), d ** -0.5),
                  (m + "shared_down", (s, d), s ** -0.5)]


def dense_leaves(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], object]]:
    """(path, shape, init) of every tower leaf; paths are the flax names.
    `init` is a kernel's N(0, init) stddev, or the name of a fixed start."""
    d = cfg["hidden_size"]
    out = []
    for i in range(cfg["num_hidden_layers"]):
        out += _layer_leaves(cfg, f"layers_{i}/", is_softmax(cfg, i))
    return out + [("norm_f_scale", (d,), "ones"), ("lm_head", (d, cfg["vocab_size"]), d ** -0.5)]


def leaf_groups(cfg: Dict) -> Dict[str, str]:
    """{leaf path: group}, the map both sides of the comparison sum by: `head`
    (final norm and head); a layer's `L<i>.attn` or `L<i>.kda` (with the
    sub-layer's norm), `L<i>.router` / `.experts` / `.shared` (the routed
    layer's norm rides with the shared expert)."""
    out = {}
    for path, _, _ in dense_leaves(cfg):
        if not path.startswith("layers_"):
            out[path] = "head"
            continue
        layer, _, rest = path.partition("/")
        i = int(layer.split("_")[1])
        if rest.startswith(("attn/", "kda/", "mix_norm")):
            part = "attn" if is_softmax(cfg, i) else "kda"
        else:
            part = next((k for k in ("router", "experts") if "moe/" + k in rest), "shared")
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


def init_leaf(keys: Dict, cfg: Dict, path: str, shape, init) -> jax.Array:
    if init == "A_log":  # log of uniform(1, 16): the near-normal draw through its distribution function
        z = weights.dense_leaf(keys["dense/" + path], shape, 1.0)
        return jnp.log(1.0 + 15.0 * 0.5 * (1.0 + jax.lax.erf(z / math.sqrt(2.0))))
    if init == "dt_bias":  # softplus^-1 of time steps log-spaced over [0.001, 0.1]
        dt = jnp.exp(jnp.linspace(math.log(1e-3), math.log(1e-1), shape[0], dtype=jnp.float32))
        return dt + jnp.log(-jnp.expm1(-dt))
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


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, *, reset_every: int = 0, delta: bool = True):
    """S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T;
    o_t = S_t^T q_t, position by position. q, k, g (B, L, H, Dk); v (B, L, H,
    Dv); beta (B, L, H) -> (B, L, H, Dv). `reset_every` > 0 zeroes the state
    at every multiple of it, `delta` False drops the -beta k k^T factor (two
    faults)."""
    bt, length, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-length) % SCAN_BLOCK
    feed = [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2)) for t in (q, k, v, g, beta)]
    blocks = (length + pad) // SCAN_BLOCK
    feed = [jnp.moveaxis(t, 1, 0).reshape((blocks, SCAN_BLOCK) + t.shape[:1] + t.shape[2:]) for t in feed]
    feed.append(jnp.arange(length + pad).reshape(blocks, SCAN_BLOCK))

    def step(s, f):
        qt, kt, vt, gt, bt_, t = f
        if reset_every:
            s = jnp.where(t % reset_every == 0, 0.0, s)
        s = s * jnp.exp(gt)[..., None]                               # Diag(alpha) S
        seen = jnp.sum(kt[..., None] * s, axis=-2) if delta else 0.0  # S^T k, (B, H, Dv)
        s = s + (bt_[..., None] * kt)[..., None] * (vt - seen)[..., None, :]
        return s, jnp.sum(qt[..., None] * s, axis=-2)

    @jax.checkpoint
    def block(s, f):
        return jax.lax.scan(step, s, f)

    _, o = jax.lax.scan(block, jnp.zeros((bt, h, dk, dv), jnp.float32), tuple(feed))
    return jnp.moveaxis(o.reshape((length + pad, bt, h, dv)), 0, 1)[:, :length]


def linear_attention(p, x, cfg, precision, fault):
    lin = cfg["linear_attn_config"]
    h, hd, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    b, s, _ = x.shape

    def conv(y, w):  # tap j reads position t - (taps - 1) + j
        padded = jnp.pad(y, ((0, 0), (taps - 1, 0), (0, 0)))
        return _silu(sum(padded[:, j:j + s] * w[j] for j in range(taps))).reshape(b, s, h, hd)

    w = jnp.split(p["conv_kernel"], 3, axis=-1)
    q, k, v = (conv(_mm("bsd,de->bse", x, p[n + "/kernel"], precision), w[i])
               for i, n in enumerate(("q_proj", "k_proj", "v_proj")))
    q, k = _l2(q) / math.sqrt(hd), _l2(k)
    f = _mm("bsr,re->bse", _mm("bsd,dr->bsr", x, p["f_a/kernel"], precision), p["f_b/kernel"], precision)
    g = -jnp.exp(p["A_log"])[:, None] * jax.nn.softplus(f + p["dt_bias"]).reshape(b, s, h, hd)
    if fault == "no_decay":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(_mm("bsd,dh->bsh", x, p["b_proj/kernel"], precision))
    if cfg["kda_allow_neg_eigval"] and fault != "beta_unscaled":
        beta = 2.0 * beta
    o = delta_rule(q, k, v, g, beta, reset_every=cfg["chunk_size"] if fault == "chunk_reset" else 0,
                   delta=fault != "no_delta")
    o = _rms(o, p["o_norm_scale"], cfg["rms_norm_eps"])
    if fault != "no_gate":
        gate = _mm("bsr,re->bse", _mm("bsd,dr->bsr", x, p["g_a/kernel"], precision), p["g_b/kernel"], precision)
        o = o * jax.nn.sigmoid(gate).reshape(b, s, h, hd)
    return _mm("bse,ed->bsd", o.reshape(b, s, h * hd), p["o_proj/kernel"], precision)


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

    block = min(block, s)
    whole = s // block * block  # the blocks of equal size as one loop, what is left as a last block
    o = jax.lax.map(lambda a: rows(*a), (q[:, :whole].reshape(b, -1, block, hq, d).swapaxes(0, 1),
                                         jnp.arange(0, whole, block)))
    o = o.swapaxes(0, 1).reshape(b, whole, hq, d)
    if whole < s:
        o = jnp.concatenate([o, rows(q[:, whole:], whole)], axis=1)
    o = o.reshape(b, s, hq * d)
    if cfg["use_gqa_gate"] and fault != "no_gate":
        o = o * jax.nn.sigmoid(_mm("bsd,de->bse", x, p["g_proj/kernel"], precision))
    return _mm("bse,ed->bsd", o, p["o_proj/kernel"], precision)


def _swiglu_mlp(x, gate, up, down, precision):
    g = _mm("td,df->tf", x, gate, precision)
    return _mm("tf,fd->td", g * jax.nn.sigmoid(g) * _mm("td,df->tf", x, up, precision), down, precision)


def _route(p, xt, cfg):
    score = jax.nn.sigmoid(jnp.matmul(xt, p["router_kernel"], precision=HI))
    _, chosen = jax.lax.top_k(score + jax.lax.stop_gradient(p["router_correction_bias"]),
                              cfg["num_experts_per_tok"])
    return score, chosen


def experts(p, x, cfg, precision, fault):
    """The shared expert's term plus those of the experts held:
    [expert_offset, expert_offset + n_routed_experts) of `router_width`."""
    b, s, d = x.shape
    xt = x.reshape(b * s, d)
    first, count = cfg["expert_offset"], cfg["n_routed_experts"]
    score, chosen = _route(p, xt, cfg)
    gate = jnp.take_along_axis(score, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-20)
    gate = gate * cfg["routed_scaling_factor"]
    out = _swiglu_mlp(xt, p["shared_gate"], p["shared_up"], p["shared_down"], precision)
    if fault != "no_routed":
        keep = jnp.arange(b * s) % 8 != (7 if fault == "drop_eighth" else 8)

        @jax.checkpoint  # keeps the expert's weights alone; its hidden states are made again
        def term(held):  # a dense mask: this expert's weight for every token (0 where not chosen)
            e, w_gate, w_up, w_down = held
            w = jnp.sum(jnp.where((chosen == first + e) & keep[:, None], gate, 0.0), axis=-1)
            return w[:, None] * _swiglu_mlp(xt, w_gate, w_up, w_down, precision)

        def add(out, held):
            return out + term(held), None

        out = jax.lax.scan(add, out, (jnp.arange(count), p["experts_gate"], p["experts_up"], p["experts_down"]))[0]
    return out.reshape(b, s, d)


def pairs_held(p, x, cfg):
    """How many (token, choice) pairs the router sends the experts held."""
    first, count = cfg["expert_offset"], cfg["n_routed_experts"]
    _, chosen = _route(p, x.reshape(-1, x.shape[-1]), cfg)
    return jnp.sum((chosen >= first) & (chosen < first + count))


def _sub(dense_p: Dict, prefix: str) -> Dict:
    return {k[len(prefix):]: v for k, v in dense_p.items() if k.startswith(prefix)}


def decoder_layer(lp, x, cfg, softmax, precision, fault):
    """-> (x, the pairs routed to held experts)."""
    eps = cfg["rms_norm_eps"]
    h = _rms(x, lp["mix_norm_scale"], eps)
    if softmax:
        x = x + attention(_sub(lp, "attn/"), h, cfg, precision, fault)
    else:
        x = x + linear_attention(_sub(lp, "kda/"), h, cfg, precision, fault)
    h = _rms(x, lp["ffn_norm_scale"], eps)
    mp = _sub(lp, "moe/")
    return x + experts(mp, h, cfg, precision, fault), pairs_held(mp, h, cfg)


def forward(dense_p, rows, cfg, precision="f32", fault=""):
    """-> (logits (B, S, V), the pairs routed to held experts in each layer)."""
    x, pairs = rows, []
    for i in range(cfg["num_hidden_layers"]):
        x, sent = jax.checkpoint(
            lambda x, lp, softmax=is_softmax(cfg, i): decoder_layer(lp, x, cfg, softmax, precision, fault))(
                x, _sub(dense_p, f"layers_{i}/"))
        pairs.append(sent)
    logits = _mm("bsd,dv->bsv", _rms(x, dense_p["norm_f_scale"], cfg["rms_norm_eps"]), dense_p["lm_head"],
                 precision)
    return logits, jnp.stack(pairs)


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


def train_step(cfg: Dict, precision: str = "f32", fault: str = ""):
    """-> step((dense, their accumulators, rows, theirs), idx (B, S), labels, weight)
    -> (the state after one step of dense Adagrad, (loss, pairs held a layer))."""
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

    return step


def follow(seed: int, cfg: Dict, chips: int, ids: np.ndarray, idx: np.ndarray, labels: np.ndarray,
           masks: np.ndarray, *, precision: str = "f32", fault: str = "") -> Dict:
    """Follow the K stacked steps from the seed. `ids` (N,) the sorted unique
    token ids padded to a fixed N; `idx` (K, B, S) positions into it; `labels`
    (K, B, S); `masks` (3, N) as `reference/deepfm.py` has them.
    -> losses (K,); `pairs_held` (K, layers); per leaf GROUP (`leaf_groups`)
    and per table four sums of squares: the gradients Adagrad received (acc_end
    - acc_start), those on the rows only step 1 touches, the parameters'
    change, and that change on the rows only the first three steps touch. One
    jitted step at a time (the state donated), then one jitted summary that
    makes the start values again, leaf by leaf."""
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
    step = jax.jit(train_step(cfg, precision, fault), donate_argnums=0)
    per_step = []
    for k in range(idx.shape[0]):
        state, out = step(state, idx[k], labels[k], weight)
        per_step.append(out)
    out = jax.jit(summary)(state, keys, ids, masks)
    out["losses"] = jnp.stack([o[0] for o in per_step])
    out["pairs_held"] = jnp.stack([o[1] for o in per_step])  # (K, layers)
    return out
