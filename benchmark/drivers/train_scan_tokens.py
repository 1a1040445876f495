"""Driver `train_scan_tokens`: `train_scan`'s closed loop (K staged batches,
the program's own K-step scan back to back, two in flight, each fenced on its
losses) for a language model behind its token table. A batch is
`batch_per_chip` sequences of `sequence_length` tokens: ids Zipf over the
configuration's vocabulary slice from the seed (the generator that is there,
one field), labels the same stream shifted by one. One EXAMPLE of
`train_examples_per_s_per_chip` is one sequence.

What differs from `train_scan`, and why: the tower has 623M parameters where
DeepFM's has 0.5M, so the state's shapes come from `jax.eval_shape` (no
second copy of the state is ever made), the comparison's sums over the tower
are taken on the device, leaf by leaf, against start values made again from
the seed (nothing of 5 GB crosses to the host), and they are summed by leaf
GROUP (`reference/<family>.py: leaf_groups`), which is what the reference
hands back. After every fence the window's counters go through the
program's own `record_window_stats`, where the program has one.
"""

from __future__ import annotations

import importlib
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import generators
from benchmark.drivers import train_scan
from benchmark.drivers.train_scan import EARLY_STEPS, _path, ref_summary

# configuration key -> `make_<family>` keyword, where the names differ
_RENAMED = {"router_width": "n_routed_experts", "n_routed_experts": "experts_held",
            "layer_norm_epsilon": "eps", "vocab_size": "vocabulary"}
_SAME = ("hidden_size", "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
         "conv_kernel", "chunk_size", "num_attention_heads", "num_key_value_heads", "head_dim",
         "num_experts_per_tok", "moe_intermediate_size", "moe_shared_expert_intermediate_size",
         "expert_offset", "routed_scaling_factor", "norm_topk_prob", "working_pairs",
         "attention_block")


def build_model(cfg: Dict):
    from openembedding_tpu import models
    kw = {k: cfg[k] for k in _SAME}
    kw.update({new: cfg[old] for old, new in _RENAMED.items()})
    return getattr(models, "make_" + cfg["family"])(
        pattern=cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]],
        compute_dtype=jnp.dtype(cfg["tower_dtype"]), **kw)


class Session(train_scan.Session):
    def setup(self):
        cfg, t = self.cfg, self.traffic
        mark = time.perf_counter()
        seq = int(t["sequence_length"])
        flat = generators.zipf_criteo_batches(
            batch_size=self.batch * (seq + 1), steps=self.k_steps, id_space=cfg["vocab_size"],
            seed=self.seed, alpha=t["zipf_alpha"], num_fields=1, dense_dim=1, feature="token")
        stream = np.stack([b["sparse"]["token"].reshape(self.batch, seq + 1) for b in flat])
        self.host = {"sparse": {"token": np.ascontiguousarray(stream[:, :, :-1])},
                     "label": np.ascontiguousarray(stream[:, :, 1:])}
        self.ids = self.host["sparse"]["token"]
        mark = self._phase("make_batches", mark)
        self._build_program()
        mark = self._phase("build_program_and_state", mark)
        self._first_dispatch(mark)
        mark = time.perf_counter()
        for _ in range(int(t["warm_dispatches"])):
            self._dispatch()
        self._phase("warm_dispatches", mark)

    def _build_program(self):
        import openembedding_tpu as embed
        from openembedding_tpu.model import Trainer
        cfg = self.cfg
        if cfg["trainer"] != "Trainer" or self.chips != 1:
            raise SystemExit("train_scan_tokens drives one chip through Trainer")
        opt = embed.Adagrad(learning_rate=cfg["learning_rate"],
                            initial_accumulator_value=cfg["adagrad_initial_accumulator"],
                            epsilon=cfg["adagrad_epsilon"])
        self.trainer = Trainer(build_model(cfg), opt)
        self.mesh = self.axis = None
        self.shards = 1
        mark = time.perf_counter()
        sample = jax.tree_util.tree_map(lambda x: x[0], self.host)
        shapes = jax.eval_shape(self.trainer.init, sample)
        self._phase("program_init", mark)
        self.keys = self.ref.make_keys(self.seed, cfg)
        self.state = jax.jit(lambda keys: self._make_state(shapes, keys))(self.keys)
        self.stacked = jax.device_put(self.host, jax.devices()[0])
        self.many = self.trainer.jit_train_many()

    def _fence(self, metrics):
        losses = super()._fence(metrics)
        record = getattr(self.trainer, "record_window_stats", None)
        if record is not None:
            record(metrics)
        return losses

    def _first_dispatch(self, mark):
        """Steps 1..K from the seed, through the window's own call and feed; then
        the sums the comparison needs, taken on the device from the state it left."""
        losses = self._dispatch()
        mark = self._phase("first_dispatch_trace_compile_or_load", mark)
        per_step = [np.unique(step) for step in self.ids]
        uniq = np.unique(np.concatenate(per_step))
        later = np.unique(np.concatenate(per_step[1:])) if self.k_steps > 1 else uniq[:0]
        n = self.ids.size  # a fixed length, so that one program serves every seed
        ids = np.zeros((n,), np.int32)
        ids[:uniq.size] = uniq
        masks = np.zeros((3, n), np.float32)
        masks[0, :uniq.size] = 1.0
        masks[1, np.searchsorted(uniq, np.setdiff1d(per_step[0], later))] = 1.0
        early = min(EARLY_STEPS, self.k_steps)  # rows that only the first three steps touch
        after = np.unique(np.concatenate(per_step[early:])) if self.k_steps > early else uniq[:0]
        masks[2, np.searchsorted(uniq, np.setdiff1d(np.unique(np.concatenate(per_step[:early])), after))] = 1.0
        self.ref_feed = {"ids": ids, "uniq": uniq, "masks": masks,
                         "touches": sum(u.size for u in per_step)}
        tables = self._probe_fn()(self.state.tables, self.keys, ids, masks)
        dense = jax.jit(self._dense_sums)(self.state.dense_params, self.state.dense_slots, self.keys)
        tables, dense = jax.device_get((tables, dense))
        self.prog = ref_summary({"losses": losses, "tables": tables, "dense": dense}, {})
        self._phase("read_state_for_comparison", mark)

    def _dense_sums(self, params, slots, keys):
        """Per leaf group [sum(acc - acc0), 0, sum((w - w0)^2), 0]: the layout
        the reference's `follow` hands back."""
        cfg, acc0 = self.cfg, self.cfg["adagrad_initial_accumulator"]
        groups = self.ref.leaf_groups(cfg)
        leaves = {p: (shape, init) for p, shape, init in self.ref.dense_leaves(cfg)}
        accs = {_path(kp[:-1]): v for kp, v in jax.tree_util.tree_flatten_with_path(slots)[0]}
        out: Dict[str, jax.Array] = {}
        for kp, w in jax.tree_util.tree_flatten_with_path(params)[0]:
            path = _path(kp)
            w0 = self.ref.init_leaf(keys, cfg, path, *leaves[path])
            g2 = jnp.sum(accs[path] - acc0)
            d2 = jnp.sum(jnp.square(w - w0))
            out[groups[path]] = out.get(groups[path], 0.0) + jnp.stack([g2, 0.0, d2, 0.0])
        return out

    def context(self) -> Dict:
        """`train_scan`'s, kept: `check` adds the reference's own count of routed
        pairs (`ref_pairs_per_layer`) to the dict the readers are handed."""
        self.ctx = super().context()
        return self.ctx

    def reference_summary(self, precision: str = "f32", fault: str = "") -> Dict:
        f = self.ref_feed
        if "idx" not in f:
            f["idx"] = np.searchsorted(f["uniq"], self.ids).astype(np.int32)
        out = self.ref.follow(self.seed, self.cfg, self.chips, f["ids"], f["idx"],
                              self.host["label"], f["masks"], precision=precision, fault=fault)
        out = jax.device_get(out)
        if precision == "f32" and not fault and getattr(self, "ctx", None) is not None and out["pairs_held"].size:
            self.ctx["ref_pairs_per_layer"] = float(np.mean(out["pairs_held"]))
        return ref_summary(out, self._grad_floor())

    def _grad_floor(self) -> Dict[str, float]:
        """Per leaf group, the norm that 4 ulps of the accumulator's start an
        element-update would leave (see `train_scan.Session._grad_floor`)."""
        ulp = float(np.spacing(np.float32(self.cfg["adagrad_initial_accumulator"])))
        updates = {"dense/" + g: n * self.k_steps for g, n in self.ref.group_sizes(self.cfg).items()}
        for name, t in self.ref.tables_of(self.cfg).items():
            updates["tables/" + name] = self.ref_feed["touches"] * t["width"]
        return {leaf: float(np.sqrt(4.0 * ulp * n)) for leaf, n in updates.items()}


def open_session(**kw) -> Session:
    ref = importlib.import_module("benchmark.reference." + kw["cfg"]["family"])
    return Session(reference=ref, **kw)
