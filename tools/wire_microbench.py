"""Micro-benchmark: fused-vs-unfused multi-table exchange + wire formats.

Measures the round-6 exchange work on the 8-virtual-device CPU mesh (real
collectives over XLA host devices — the same substrate the tier-1 suite
pins parity on; the single physical chip cannot exercise an S>1 exchange):

- step time of a 3-table / 2-dim-group model through the per-table protocol
  (9 all_to_alls, fp32) vs the fused exchange (6 all_to_alls) at fp32, bf16
  and int8 wire;
- the STATIC wire-cost model (`ops/wire.exchange_cost`): exchange bytes/step
  per format — the acceptance bound is fp32/bf16 >= 1.7x (re-anchored in
  round 13: the model now prices hash-table id slots at their true 8 B pair
  layout and the int8 in-band scale lanes, so the same exchange reads a
  slightly lower — honest — ratio than the round-6 4-B-id model's 1.8x);
- since round 13, the REAL compiled collective bytes per wire mode, counted
  from the lowered HLO with the same `collective_payloads` parser the oelint
  hlo-budget pass pins — printed next to the analytic model with the
  model-vs-HLO delta (asserted 0: the model prices what actually ships);
- pull/push parity: the bf16- and int8-wire runs must land within format
  tolerance of the fp32 run (trained table rows compared), with table
  storage still fp32.

Emits ONE BENCH-format JSON line on stdout:
  {"metric": "wire_bf16_bytes_ratio", "value": ..., "unit": "x",
   "vs_baseline": ..., "extra": {...}, "errors": {...}}

Run: python tools/wire_microbench.py [--steps 8] [--batch 256]
"""

import argparse
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU mesh by design (see module docstring) — set BEFORE jax import
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"

S = 8
VOCAB = 1 << 14
DIM = 16


def build_model():
    """3 PS tables in 2 dim-groups (the tests/test_wire.py shape at bench
    scale): dim-16 {latent (array), hashed (hash)} + dim-1 {first_order}."""
    import flax.linen as nn
    import jax.numpy as jnp
    import openembedding_tpu as embed
    from openembedding_tpu.model import EmbeddingModel

    class Tower(nn.Module):
        @nn.compact
        def __call__(self, embedded, dense):
            bias = self.param("bias", nn.initializers.zeros, (1,),
                              jnp.float32)
            out = (jnp.sum(embedded["latent"].astype(jnp.float32),
                           axis=(1, 2))
                   + jnp.sum(embedded["hashed"].astype(jnp.float32),
                             axis=(1, 2))
                   + jnp.sum(embedded["first_order"][..., 0]
                             .astype(jnp.float32), axis=1))
            return out + bias[0]

    embs = [
        embed.Embedding(VOCAB, DIM, name="latent"),
        embed.Embedding(-1, DIM, name="hashed", capacity=1 << 16),
        embed.Embedding(VOCAB, 1, name="first_order", feature="latent"),
    ]
    return EmbeddingModel(Tower(), embs)


def batches(batch, steps, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(steps):
        # Zipf-ish skew so dedup and the duplicate-count lanes do real work
        lat = (rng.zipf(1.3, (batch, 8)) % VOCAB).astype(np.int32)
        hsh = (rng.zipf(1.3, (batch, 4)).astype(np.int64) * 2654435761
               % (1 << 40))
        out.append({"sparse": {"latent": lat, "hashed": hsh},
                    "label": rng.integers(0, 2, (batch,))
                    .astype(np.float32)})
    return out


def train(wire, group_exchange, bs, steps=3):
    import jax
    import openembedding_tpu as embed
    from openembedding_tpu.parallel import MeshTrainer, make_mesh

    tr = MeshTrainer(build_model(), embed.Adagrad(learning_rate=0.1),
                     mesh=make_mesh(), wire=wire,
                     group_exchange=group_exchange)
    bs = [jax.device_put(b) for b in bs]
    state = tr.init(bs[0])
    step = tr.jit_train_step(bs[0], state)
    # compiled-HLO truth BEFORE the donating warmup call: the byte counts
    # reported next to the analytic model come from the same counter the
    # oelint hlo-budget pass pins (`collective_payloads`)
    hlo_text = step.lower(state, bs[0]).compile().as_text()
    state, m = step(state, bs[0])  # compile + warmup
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    n = 0
    for _ in range(steps):
        for b in bs:
            state, m = step(state, b)
            n += 1
    jax.block_until_ready(m["loss"])
    ms = (time.perf_counter() - t0) / n * 1e3
    return tr, state, ms, hlo_text


def probe(tr, state):
    """Trained latent-table rows (the parity comparison payload)."""
    import jax
    from functools import partial
    from jax.sharding import PartitionSpec as P
    from openembedding_tpu.parallel.sharded import sharded_lookup

    spec = tr.model.specs["latent"]
    pull = jax.jit(jax.shard_map(
        partial(sharded_lookup, spec, axis=tr.axis), mesh=tr.mesh,
        in_specs=(tr._table_pspec(spec), P()), out_specs=P(),
        check_vma=False))
    return np.asarray(pull(state.tables["latent"],
                           np.arange(VOCAB, dtype=np.int32)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--steps", type=int, default=4)
    args = ap.parse_args()

    result = {"metric": "wire_bf16_bytes_ratio", "value": None, "unit": "x",
              "vs_baseline": None}
    extra, errors = {}, {}
    try:
        from openembedding_tpu.ops import wire as wire_mod  # noqa: F401
        from tools.oelint.passes.hlo_budget import collective_payloads

        bs = batches(args.batch, args.steps)
        runs = {}
        for label, (fmt, fused) in {
            "unfused_fp32": ("fp32", False),
            "fused_fp32": ("fp32", True),
            "fused_bf16": ("bf16", True),
            "fused_int8": ("int8", True),
            # round-17 per-table mixed wire: the dim-16 group splits on
            # (dim, fmt) into an int8 and an fp32 a2a group (9 collectives,
            # not 6) — the analytic model must price every mixed-format
            # group exactly (delta 0), same as the uniform modes
            "fused_mixed": ({"latent": "int8", "*": "fp32"}, True),
        }.items():
            tr, state, ms, hlo_text = train(fmt, fused, bs)
            runs[label] = (tr, state)
            cost = tr.last_wire_cost
            # real compiled bytes from the same counter the oelint
            # hlo-budget pass pins — the analytic model must agree
            payloads = collective_payloads(hlo_text)
            hlo_a2a = sum(b for k, _, b in payloads if k == "all_to_all")
            model = (cost["bytes_per_step"]
                     + cost.get("hot_a2a_bytes", 0))
            extra[label] = {
                "step_ms": round(ms, 2),
                "collectives_per_step": cost["collectives_per_step"],
                "wire_bytes_per_step": cost["bytes_per_step"],
                "hlo_a2a_bytes": hlo_a2a,
                "hlo_a2a_dtypes": ",".join(sorted(
                    {d for k, d, _ in payloads if k == "all_to_all"})),
                "model_vs_hlo_delta": hlo_a2a - model,
            }
            print(f"[wire] {label:13s}: {ms:8.2f} ms/step, "
                  f"{cost['collectives_per_step']} a2a, "
                  f"model {cost['bytes_per_step']} B/step/device, "
                  f"HLO {hlo_a2a} B "
                  f"({extra[label]['hlo_a2a_dtypes']}), "
                  f"delta {extra[label]['model_vs_hlo_delta']}",
                  file=sys.stderr, flush=True)
            assert extra[label]["model_vs_hlo_delta"] == 0, (
                label, extra[label])

        # parity: lossy wire within format tolerance of fp32; storage fp32
        base = probe(*runs["fused_fp32"])
        exactf = probe(*runs["unfused_fp32"])
        np.testing.assert_array_equal(base, exactf)  # fusion is transparent
        for label, tol in (("fused_bf16", 0.02), ("fused_int8", 0.06),
                           ("fused_mixed", 0.06)):  # latent rides int8
            got = probe(*runs[label])
            err = np.abs(got - base).max()
            scale = max(np.abs(base).max(), 1e-6)
            extra[label]["max_abs_err_vs_fp32"] = float(err)
            assert err <= tol * scale + tol, (label, err)
            ts = runs[label][1].tables["latent"]
            assert str(ts.weights.dtype) == "float32"
        extra["parity"] = "fused==unfused bit-exact; bf16/int8 within tol"

        ratio = (extra["fused_fp32"]["wire_bytes_per_step"]
                 / extra["fused_bf16"]["wire_bytes_per_step"])
        result["value"] = round(ratio, 3)
        # vs_baseline: the acceptance floor (>= 1.7x fewer exchange bytes;
        # see module docstring for the round-13 re-anchor)
        result["vs_baseline"] = round(ratio / 1.7, 3)
        extra["int8_bytes_ratio"] = round(
            extra["fused_fp32"]["wire_bytes_per_step"]
            / extra["fused_int8"]["wire_bytes_per_step"], 3)
        extra["fused_speedup_fp32"] = round(
            extra["unfused_fp32"]["step_ms"]
            / extra["fused_fp32"]["step_ms"], 3)
    except Exception as e:  # noqa: BLE001 — recorded in the stanza
        errors["wire"] = f"{type(e).__name__}: {e}"[:500]
        traceback.print_exc(file=sys.stderr)

    if extra:
        result["extra"] = extra
    if errors:
        result["errors"] = errors
    print(json.dumps(result), flush=True)
    return 0 if result["value"] is not None and not errors else 1


if __name__ == "__main__":
    sys.exit(main())
