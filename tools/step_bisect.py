"""Bisect the DeepFM train step on-device with RELIABLE fences.

Each timed fn is wrapped in lax.scan over K iterations inside ONE jit dispatch and
returns a scalar that depends on everything; timing = (fetch latency of that
scalar) — dispatch overhead and unreliable block_until_ready semantics through the
remote runtime cannot distort per-iteration numbers this way.
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

K = 20


def timeit_scan(make_body, init_carry, label):
    import jax
    import jax.numpy as jnp

    def run(carry):
        def body(c, _):
            return make_body(c), None
        c, _ = jax.lax.scan(body, carry, None, length=K)
        return jax.tree_util.tree_reduce(
            lambda a, x: a + jnp.sum(x).astype(jnp.float32), c,
            jnp.float32(0))

    fn = jax.jit(run)
    float(fn(init_carry))  # compile + warm
    t0 = time.perf_counter()
    float(fn(init_carry))
    dt = (time.perf_counter() - t0) / K * 1e3
    print(f"{label:34s} {dt:8.3f} ms/iter", flush=True)
    return dt


def main():
    import jax
    import jax.numpy as jnp
    import openembedding_tpu as embed
    from openembedding_tpu.model import Trainer, dense_apply
    from openembedding_tpu.models import make_deepfm
    from openembedding_tpu.data import synthetic_criteo
    from openembedding_tpu.ops.dedup import unique_with_counts, bucket_by_owner
    from openembedding_tpu.ops.sparse import (lookup_rows,
                                              sparse_apply_dense_table)

    print(f"backend={jax.default_backend()}", flush=True)
    VOCAB, DIM, BATCH = 1 << 24, 9, 4096
    model = make_deepfm(vocabulary=VOCAB, dim=DIM)
    trainer = Trainer(model, embed.Adagrad(learning_rate=0.05))
    batch = jax.device_put(next(synthetic_criteo(
        BATCH, id_space=VOCAB, steps=1, seed=7, ids_dtype=np.int32)))
    state = trainer.init(batch)
    ids = batch["sparse"]["categorical"].reshape(-1)
    table = state.tables["categorical"]
    opt = trainer.optimizer

    # 0. whole train step (scan-fused), for reference
    def full(carry):
        st, b = carry
        st, _ = trainer.train_step(st, b)
        return (st, b)
    timeit_scan(full, (state, batch), "full train_step")

    # 1. dedup only (carry the ids so scan can't hoist)
    def dedup(carry):
        u = unique_with_counts(carry)
        return carry + u.inverse.astype(carry.dtype)
    timeit_scan(dedup, ids, "dedup (unique_with_counts)")

    # 1b. fused dedup + owner routing (the round-4 exchange plan: one
    # multi-key sort; compare against 1 + a second bucket_by_owner sort)
    from openembedding_tpu.ops.dedup import unique_and_route

    def fused_route(carry):
        u, b = unique_and_route(carry, carry >= 0, 8, carry.shape[0] // 8)
        return carry + u.inverse.astype(carry.dtype) + b.owner.astype(
            carry.dtype)
    timeit_scan(fused_route, ids, "dedup+route fused (unique_and_route S=8)")

    def split_route(carry):
        u = unique_with_counts(carry)
        b = bucket_by_owner(u.unique_ids, u.counts > 0, 8,
                            carry.shape[0] // 8)
        return carry + u.inverse.astype(carry.dtype) + b.owner.astype(
            carry.dtype)
    timeit_scan(split_route, ids, "dedup+route split (2 sorts, r3 protocol)")

    # 2. gather only
    def gather(carry):
        rows = lookup_rows(table.weights, carry)
        return carry + rows[:, 0].astype(carry.dtype)
    timeit_scan(gather, ids, "gather rows")

    # 3. sparse apply only (weights+slots carried)
    grads = jnp.ones((ids.shape[0], DIM + 1), jnp.float32)

    def apply_fn(carry):
        w, s = carry
        w, s = sparse_apply_dense_table(opt, w, s, ids, grads)
        return (w, s)
    timeit_scan(apply_fn, (table.weights, table.slots), "sparse apply")

    # 3b. PACKED sparse apply (the train_many scan layout): one gather/scatter
    # pair over the concatenated weights+slots array (ops/sparse.packed_layout)
    from openembedding_tpu.ops.sparse import (pack_table, packed_layout,
                                              sparse_apply_packed_table)
    lay = packed_layout(DIM + 1, table.slots, table.weights.dtype)
    if lay is not None:
        packed = pack_table(table.weights, table.slots, lay)

        def papply(carry):
            return sparse_apply_packed_table(opt, carry, lay, DIM + 1, ids,
                                             grads)[0]
        timeit_scan(papply, packed, "sparse apply PACKED")

        # 0b. whole train step on the packed state (what train_many scans)
        layouts = trainer._packed_layouts(state)
        ptables = dict(state.tables)
        for name, l in layouts.items():
            ts = ptables[name]
            ptables[name] = ts.replace(
                weights=pack_table(ts.weights, ts.slots, l), slots={})
        pstate = state.replace(tables=ptables)

        def full_packed(carry):
            st, b = carry
            st, _ = trainer.train_step(st, b, packed=layouts)
            return (st, b)
        timeit_scan(full_packed, (pstate, batch), "full train_step PACKED")

    # 4. dense fwd+bwd only
    rows = jnp.ones((BATCH, 26, DIM + 1), jnp.float32)

    def fwdbwd(carry):
        p = carry

        def loss_fn(p, r):
            logits = model.module.apply({"params": p}, {"categorical": r},
                                        batch["dense"])
            return model.loss_fn(logits, batch["label"])
        _, (gp, gr) = jax.value_and_grad(loss_fn, argnums=(0, 1))(p, rows)
        return jax.tree_util.tree_map(lambda a, b: a + 0e0 * b, p, gp)
    timeit_scan(fwdbwd, state.dense_params, "dense fwd+bwd")

    # 5. dense apply only
    dgrads = jax.tree_util.tree_map(jnp.ones_like, state.dense_params)

    def dapply(carry):
        p, s = carry
        return dense_apply(opt, p, s, dgrads)
    timeit_scan(dapply, (state.dense_params, state.dense_slots), "dense apply")


if __name__ == "__main__":
    main()
