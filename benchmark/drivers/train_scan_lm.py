"""Driver `train_scan_lm`: `train_scan_tokens`'s session (the batches of token
ids, the K-step scan back to back, the fence, the staging, the sums for the
comparison and the reference's feed, all inherited) for any language-model
family behind a token table. What differs is the program's construction
alone: `train_scan_tokens.build_model` names NemotronH's configuration keys;
here the configuration's keys become `make_<family>`'s keywords through a
table kept by family, so the next family is a row and no third driver. And
the state made from the seed is COMMITTED to its device, as the state every
scan hands back is: an uncommitted first state gives the first dispatch
another signature than every later one, and the scan compiles twice (a
second compile of 75 s in this cell, whose executable the compile cache
cannot hold; my chip runs, PR 32).
"""

from __future__ import annotations

import importlib
import time
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from benchmark.drivers import train_scan_tokens

# family -> (configuration keys passed under their own names,
#            {configuration key: `make_<family>` keyword} where the names differ)
KEYWORDS = {
    "joyai_flash": (
        ("hidden_size", "num_hidden_layers", "first_k_dense_replace", "num_nextn_predict_layers",
         "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "rope_theta", "intermediate_size", "num_experts_per_tok", "moe_intermediate_size",
         "n_shared_experts", "expert_offset", "routed_scaling_factor", "norm_topk_prob", "working_pairs",
         "attention_block", "mtp_loss_weight"),
        {"router_width": "n_routed_experts", "n_routed_experts": "experts_held", "rms_norm_eps": "eps",
         "vocab_size": "vocabulary"}),
}


def build_model(cfg: Dict):
    from openembedding_tpu import models
    if cfg["family"] not in KEYWORDS:
        raise SystemExit(f"train_scan_lm has no keyword table for family {cfg['family']!r}")
    same, renamed = KEYWORDS[cfg["family"]]
    kw = {k: cfg[k] for k in same}
    kw.update({new: cfg[old] for old, new in renamed.items()})
    return getattr(models, "make_" + cfg["family"])(compute_dtype=jnp.dtype(cfg["tower_dtype"]), **kw)


class Session(train_scan_tokens.Session):
    def _build_program(self):
        """`train_scan_tokens.Session._build_program` with this module's `build_model`."""
        import openembedding_tpu as embed
        from openembedding_tpu.model import Trainer
        cfg = self.cfg
        if cfg["trainer"] != "Trainer" or self.chips != 1:
            raise SystemExit("train_scan_lm drives one chip through Trainer")
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
        device = jax.devices()[0]
        self.state = jax.jit(lambda keys: self._make_state(shapes, keys),
                             out_shardings=SingleDeviceSharding(device))(self.keys)
        self.stacked = jax.device_put(self.host, device)
        self.many = self.trainer.jit_train_many()


def open_session(**kw) -> Session:
    ref = importlib.import_module("benchmark.reference." + kw["cfg"]["family"])
    return Session(reference=ref, **kw)
