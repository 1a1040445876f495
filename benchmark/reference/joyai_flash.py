"""Plain JoyAI-LLM-Flash training step (a DeepSeek-V3-style decoder: latent
attention, a leading dense SwiGLU layer, routed SwiGLU experts, one
multi-token-prediction module): float32 `jax.numpy`, matmuls at `highest`, no
kernels, no packing, no blockwise softmax, no dispatch. Imports nothing of the
program and takes nothing the program made: rows and tower come from
`benchmark.weights`.

x_0 = table[token]; layer l: x += Attn(RMSNorm(x)); x += FFN(RMSNorm(x)); no
bias anywhere, eps `rms_norm_eps`.
  Attn  c_q = RMSNorm(x W_qa); q = c_q W_qb, a head [q_nope ; q_rot];
        [c_kv ; k_rot] = x W_kva; c_kv = RMSNorm(c_kv); [k_nope ; v] a head =
        c_kv W_kvb; q_rot, k_rot turned by rotary positions over INTERLEAVED
        pairs (x_2i, x_2i+1), angle pos * theta^(-2i/R), from explicit cos /
        sin tables made in float64 (no scaling: `rope_scaling` null); k_rot one
        vector shared by the heads; softmax(q k^T / sqrt(nope + rot) + causal
        mask) v with every key in the softmax (a block of queries at a time, one
        loop over the blocks; keys and values expanded per head: the
        non-absorbed form); W_o.
  FFN   layer < `first_k_dense_replace`: W_down(silu(W_gate x) * (W_up x));
        else s = sigmoid(x W_r); the top k of s + correction bias (`n_group` =
        `topk_group` = 1: no group masked); weights = chosen s / their sum *
        routed_scaling_factor; every HELD expert (`n_routed_experts` of the
        file, offset `expert_offset`, of the router's `router_width`) runs on
        every token, one after another (a `lax.scan`: unrolled, the step takes
        four minutes to compile), times a dense mask of its weight; absent experts add
        nothing; plus the shared expert; all SwiGLU.
logits = RMSNorm(x) W_head. The prediction module (depth 1): position t takes
h'_t = W_eh [RMSNorm_e(row of position t + 1) ; RMSNorm_h(h_t)], h_t the last
held layer's output before the final norm, one more decoder layer (routed),
its own final RMSNorm, the same W_head: logits for token t + 2 = label[t + 1].
loss = xent(main) + `mtp_loss_weight` * xent(module) over the S - 1 positions
that have such a label (the last position is fed zeros and weighs 0).
Dense Adagrad on every leaf and on the touched rows, duplicates summed first.

Departures from the published description (each a line under `assumed` in the
configuration): the merge's column order (embedding first) and h_t taken
before the stack's final norm (the report leaves both open); `mtp_loss_weight`
0.3 (no such key is published; DeepSeek-V3's pre-training value).

`precision`: "f32" the reference; "tower_fp8" feeds every matrix product of
activations float8_e4m3 inputs (the router stays f32); "table_bf16" keeps rows
and their accumulators in bfloat16. `fault`: "half_batch" (the second half of
every sequence weightless), "no_routed" (the routed experts' terms left out),
"drop_eighth" (every eighth token dropped at dispatch), "noncausal" (attention
without its mask), "no_rope" (rotary positions left out), "no_mtp" (the second
loss term left out), "mtp_unshifted" (the module fed position t's own row).
"""

from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import weights

FAMILY = "joyai_flash"
CONTROLS = ("tower_fp8", "table_bf16")
FAULTS = ("half_batch", "no_routed", "drop_eighth", "noncausal", "no_rope", "no_mtp", "mtp_unshifted")
HI = jax.lax.Precision.HIGHEST


def tables_of(cfg: Dict) -> Dict[str, Dict]:
    return {"token": {"width": cfg["hidden_size"], "zero_cols": 0}}


def _layer_leaves(cfg: Dict, p: str, routed: bool) -> List[Tuple[str, Tuple[int, ...], object]]:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    n, r, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    a = p + "attn/"
    out = [(p + "attn_norm_scale", (d,), "ones"),
           (a + "q_a/kernel", (d, qr), d ** -0.5), (a + "q_norm_scale", (qr,), "ones"),
           (a + "q_b/kernel", (qr, h * (n + r)), qr ** -0.5),
           (a + "kv_a/kernel", (d, kvr + r), d ** -0.5), (a + "kv_norm_scale", (kvr,), "ones"),
           (a + "kv_b/kernel", (kvr, h * (n + v)), kvr ** -0.5),
           (a + "o_proj/kernel", (h * v, d), (h * v) ** -0.5),
           (p + "ffn_norm_scale", (d,), "ones")]
    if not routed:
        i = cfg["intermediate_size"]
        return out + [(p + "mlp_gate", (d, i), d ** -0.5), (p + "mlp_up", (d, i), d ** -0.5),
                      (p + "mlp_down", (i, d), i ** -0.5)]
    m = p + "moe/"
    e, f = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    s = cfg["n_shared_experts"] * f
    return out + [(m + "router_kernel", (d, cfg["router_width"]), d ** -0.5),
                  (m + "router_correction_bias", (cfg["router_width"],), "zeros"),
                  (m + "experts_gate", (e, d, f), d ** -0.5), (m + "experts_up", (e, d, f), d ** -0.5),
                  (m + "experts_down", (e, f, d), f ** -0.5),
                  (m + "shared_gate", (d, s), d ** -0.5), (m + "shared_up", (d, s), d ** -0.5),
                  (m + "shared_down", (s, d), s ** -0.5)]


def has_mtp(cfg: Dict) -> bool:
    return bool(cfg["num_nextn_predict_layers"])


def dense_leaves(cfg: Dict) -> List[Tuple[str, Tuple[int, ...], object]]:
    """(path, shape, init) of every tower leaf; paths are the flax names.
    `init` is a kernel's N(0, init) stddev, or the name of a fixed start."""
    d = cfg["hidden_size"]
    out = []
    for i in range(cfg["num_hidden_layers"]):
        out += _layer_leaves(cfg, f"layers_{i}/", i >= cfg["first_k_dense_replace"])
    if has_mtp(cfg):
        out += [("mtp/enorm_scale", (d,), "ones"), ("mtp/hnorm_scale", (d,), "ones"),
                ("mtp/eh_proj/kernel", (2 * d, d), (2 * d) ** -0.5)]
        out += _layer_leaves(cfg, "mtp/layer/", True)
        out.append(("mtp_norm_scale", (d,), "ones"))
    return out + [("norm_f_scale", (d,), "ones"), ("lm_head", (d, cfg["vocab_size"]), d ** -0.5)]


def leaf_groups(cfg: Dict) -> Dict[str, str]:
    """{leaf path: group}, the map both sides of the comparison sum by: `head`
    (final norm and head); a layer's `L<i>.attn`, and `L<i>.mlp` or `L<i>.router`
    / `.experts` / `.shared`; the module's `mtp.merge` (its three norms and
    W_eh), `mtp.attn`, `mtp.router` / `.experts` / `.shared`. A sub-block's own
    norm rides with it (the routed layer's with the shared expert)."""
    out = {}
    for path, _, _ in dense_leaves(cfg):
        if path.startswith("layers_"):
            layer, _, rest = path.partition("/")
            i = int(layer.split("_")[1])
            prefix, routed = f"L{i}", i >= cfg["first_k_dense_replace"]
        elif path.startswith("mtp/layer/"):
            prefix, rest, routed = "mtp", path[len("mtp/layer/"):], True
        else:
            out[path] = "mtp.merge" if path.startswith("mtp") else "head"
            continue
        if rest.startswith("attn"):
            part = "attn"
        elif not routed:
            part = "mlp"
        else:
            part = next((k for k in ("router", "experts") if "moe/" + k in rest), "shared")
        out[path] = f"{prefix}.{part}"
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


def rope_tables(seq: int, width: int, theta: float):
    """cos, sin (seq, width / 2) of pos * theta^(-2i / width), made in float64."""
    inv = float(theta) ** (-2.0 * np.arange(width // 2, dtype=np.float64) / width)
    ang = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def rope(x, theta: float):
    """x (B, S, H, R): the pair (x_2i, x_2i+1) of position s turned by angle[s, i]."""
    cos, sin = (t[None, :, None, :] for t in rope_tables(x.shape[1], x.shape[-1], theta))
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def attention(p, x, cfg, precision, fault, block=512):
    b, s, _ = x.shape
    h, kvr = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    n, r, v = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    eps = cfg["rms_norm_eps"]
    c_q = _rms(_mm("bsd,de->bse", x, p["q_a/kernel"], precision), p["q_norm_scale"], eps)
    q = _mm("bse,ef->bsf", c_q, p["q_b/kernel"], precision).reshape(b, s, h, n + r)
    ckv = _mm("bsd,de->bse", x, p["kv_a/kernel"], precision)
    c_kv = _rms(ckv[..., :kvr], p["kv_norm_scale"], eps)
    kv = _mm("bse,ef->bsf", c_kv, p["kv_b/kernel"], precision).reshape(b, s, h, n + v)
    q_rot, k_rot = q[..., n:], ckv[..., None, kvr:]
    if fault != "no_rope":
        q_rot, k_rot = rope(q_rot, cfg["rope_theta"]), rope(k_rot, cfg["rope_theta"])
    q = jnp.concatenate([q[..., :n], q_rot], axis=-1)
    k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(k_rot, (b, s, h, r))], axis=-1)
    val = kv[..., n:]

    @jax.checkpoint
    def rows(qb, lo):
        sc = _mm("bqhd,bkhd->bhqk", qb, k, precision) / math.sqrt(n + r)
        if fault != "noncausal":
            sc = jnp.where((lo + jnp.arange(qb.shape[1]))[:, None] >= jnp.arange(s)[None, :], sc, -jnp.inf)
        return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), val, precision)

    block = min(block, s)
    whole = s // block * block  # the blocks of equal size as one loop, what is left as a last block
    o = jax.lax.map(lambda a: rows(*a), (q[:, :whole].reshape(b, -1, block, h, n + r).swapaxes(0, 1),
                                         jnp.arange(0, whole, block)))
    o = o.swapaxes(0, 1).reshape(b, whole, h, v)
    if whole < s:
        o = jnp.concatenate([o, rows(q[:, whole:], whole)], axis=1)
    return _mm("bse,ed->bsd", o.reshape(b, s, h * v), p["o_proj/kernel"], precision)


def _swiglu_mlp(x, gate, up, down, precision):
    g = _mm("td,df->tf", x, gate, precision)
    return _mm("tf,fd->td", g * jax.nn.sigmoid(g) * _mm("td,df->tf", x, up, precision), down, precision)


def dense_mlp(p, x, cfg, precision, fault):
    b, s, d = x.shape
    return _swiglu_mlp(x.reshape(b * s, d), p["mlp_gate"], p["mlp_up"], p["mlp_down"], precision).reshape(b, s, d)


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


def decoder_layer(lp, x, cfg, routed, precision, fault):
    """-> (x, the pairs routed to held experts; None for the dense layer)."""
    eps = cfg["rms_norm_eps"]
    x = x + attention(_sub(lp, "attn/"), _rms(x, lp["attn_norm_scale"], eps), cfg, precision, fault)
    h = _rms(x, lp["ffn_norm_scale"], eps)
    if not routed:
        return x + dense_mlp(lp, h, cfg, precision, fault), None
    mp = _sub(lp, "moe/")
    return x + experts(mp, h, cfg, precision, fault), pairs_held(mp, h, cfg)


def forward(dense_p, rows, cfg, precision="f32", fault=""):
    """-> (main logits (B, S, V), the module's logits or None, the pairs routed
    to held experts in each routed layer, the module's last)."""
    eps = cfg["rms_norm_eps"]
    x, pairs = rows, []
    for i in range(cfg["num_hidden_layers"]):
        routed = i >= cfg["first_k_dense_replace"]
        x, sent = jax.checkpoint(
            lambda x, lp, routed=routed: decoder_layer(lp, x, cfg, routed, precision, fault))(
                x, _sub(dense_p, f"layers_{i}/"))
        if routed:
            pairs.append(sent)
    main = _mm("bsd,dv->bsv", _rms(x, dense_p["norm_f_scale"], eps), dense_p["lm_head"], precision)
    nxt = None
    if has_mtp(cfg):
        mp = _sub(dense_p, "mtp/")
        after = rows if fault == "mtp_unshifted" else jnp.pad(rows[:, 1:], ((0, 0), (0, 1), (0, 0)))
        merged = jnp.concatenate([_rms(after, mp["enorm_scale"], eps), _rms(x, mp["hnorm_scale"], eps)], axis=-1)
        h = _mm("bse,ed->bsd", merged, mp["eh_proj/kernel"], precision)
        h, sent = jax.checkpoint(lambda h, lp: decoder_layer(lp, h, cfg, True, precision, fault))(
            h, _sub(mp, "layer/"))
        pairs.append(sent)
        nxt = _mm("bsd,dv->bsv", _rms(h, dense_p["mtp_norm_scale"], eps), dense_p["lm_head"], precision)
    return main, nxt, jnp.stack(pairs) if pairs else jnp.zeros((0,))


def logits_fn(dense_p, rows, cfg, precision="f32", fault=""):
    return forward(dense_p, rows, cfg, precision, fault)[:2]


def xent(logits, labels, weight):
    per = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(per * weight) / jnp.sum(weight)


def losses(main, nxt, labels, weight, cfg, fault=""):
    """-> (loss, main term, module's term): the module's logits at t against
    labels at t + 1, over the positions that have one."""
    l_main = xent(main, labels, weight)
    if nxt is None:
        return l_main, l_main, jnp.zeros(())
    seq = labels.shape[1]
    has_next = (jnp.arange(seq) < seq - 1).astype(jnp.float32)
    l_mtp = xent(nxt, jnp.roll(labels, -1, axis=1), weight * has_next)
    lam = 0.0 if fault == "no_mtp" else cfg["mtp_loss_weight"]
    return l_main + lam * l_mtp, l_main, l_mtp


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
    -> losses (K,); `loss_terms` (K, 2) the main and the module's term;
    `pairs_held` (K, routed layers, the module's last); per leaf GROUP
    (`leaf_groups`) and per table four sums of squares: the gradients Adagrad
    received (acc_end - acc_start), those on the rows only step 1 touches, the
    parameters' change, and that change on the rows only the first three steps
    touch. One jitted step at a time (the state donated), then one jitted
    summary that makes the start values again, leaf by leaf."""
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
            main, nxt, pairs = forward(dense_p, pulled["token"], cfg, precision, fault)
            loss, l_main, l_mtp = losses(main, nxt, y, weight, cfg, fault)
            return loss, (pairs, jnp.stack([l_main, l_mtp]))

        pulled = {n: r[ix] for n, r in rows.items()}
        (loss, aux), (gd, gr) = jax.value_and_grad(loss_fn, argnums=(0, 1), has_aux=True)(dense_p, pulled)
        new_dense, new_dacc, new_rows, new_accs = {}, {}, {}, {}
        for n in dense_p:
            new_dense[n], new_dacc[n] = _adagrad(dense_p[n], dacc[n], gd[n], cfg)
        for n in rows:
            g = jnp.zeros_like(rows[n]).at[ix].add(gr[n])
            w, a = _adagrad(rows[n], accs[n], g, cfg)
            new_rows[n], new_accs[n] = _store(w, precision), _store(a, precision)
        return (new_dense, new_dacc, new_rows, new_accs), (loss,) + aux

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
        state, out = step(state, idx[k], labels[k], weight)
        per_step.append(out)
    out = jax.jit(summary)(state, keys, ids, masks)
    out["losses"] = jnp.stack([o[0] for o in per_step])
    out["pairs_held"] = jnp.stack([o[1] for o in per_step])  # (K, routed layers)
    out["loss_terms"] = jnp.stack([o[2] for o in per_step])  # (K, 2)
    return out
