"""Driver `train_scan_lm_diffusion`: `train_scan_lm_keywords`' session (the
program's construction from the configuration's `make_keywords`, the K-step
scan back to back, the fence, the staging, the sums for the comparison, all
inherited) over BLOCK-DIFFUSION batches: a step is not ids and ids shifted.
Clean ids x_0 are Zipf over the configuration's vocabulary slice less its last
row (the mask id); a noised copy x_t masks every position of a block of
`block_length` with that block's t ~ U[`noise_t_min`, 1], drawn from the seed
on the host (`corrupt`, as `models/sdar_moe.corrupt_blocks` draws it on the
device). The batch is ids [x_t ; x_0] (B, 2L), labels x_0 (B, L) unshifted,
and as its `dense` entry each noisy position's loss weight (1 / t where
masked, 0 elsewhere). The reference is fed the weights beside the ids.

The readers are handed ids of (K, B, 2L): the length `family_step_mfu` reads
is the stack's, 2L, and `work_bd` halves it for the head and the mask.
"""

from __future__ import annotations

import importlib
import time
from typing import Dict

import jax
import numpy as np

from benchmark import generators
from benchmark.drivers import train_scan_lm_keywords
from benchmark.drivers.train_scan import ref_summary


def corrupt(seed: int, x0: np.ndarray, block: int, t_min: float, mask_id: int):
    """x0 (..., L) clean ids -> (x_t int32, weights f32), both (..., L): a
    block of `block` positions draws t ~ U[t_min, 1], each of its positions is
    `mask_id` with probability t and weighs 1 / t where masked."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0xB10C])
    t = rng.uniform(t_min, 1.0, size=x0.shape[:-1] + (x0.shape[-1] // block,))
    t = np.repeat(t, block, axis=-1).astype(np.float32)
    masked = rng.random(x0.shape) < t
    return (np.where(masked, mask_id, x0).astype(np.int32),
            np.where(masked, np.float32(1.0) / t, np.float32(0.0)).astype(np.float32))


def diffusion_batches(seed: int, steps: int, batch: int, cfg: Dict, traffic: Dict) -> Dict:
    """-> the K staged batches: {"sparse": {"token": (K, B, 2L)}, "dense":
    (K, B, L) weights, "label": (K, B, L)}."""
    seq, block = int(traffic["sequence_length"]), int(traffic["block_length"])
    if block != cfg["block_length"] or seq % block or cfg["mask_id"] != cfg["vocab_size"] - 1:
        raise SystemExit(f"noise blocks of {block} over {seq} positions for a model of blocks of "
                         f"{cfg['block_length']}, mask id {cfg['mask_id']} of {cfg['vocab_size']} rows")
    flat = generators.zipf_criteo_batches(
        batch_size=batch * seq, steps=steps, id_space=cfg["mask_id"], seed=seed,
        alpha=traffic["zipf_alpha"], num_fields=1, dense_dim=1, feature="token")
    x0 = np.stack([b["sparse"]["token"].reshape(batch, seq) for b in flat])
    xt, weight = corrupt(seed, x0, block, float(traffic["noise_t_min"]), cfg["mask_id"])
    return {"sparse": {"token": np.ascontiguousarray(np.concatenate([xt, x0], axis=-1))},
            "dense": weight, "label": x0}


class Session(train_scan_lm_keywords.Session):
    def setup(self):
        """`train_scan_tokens.Session.setup` over block-diffusion batches."""
        mark = time.perf_counter()
        self.host = diffusion_batches(self.seed, self.k_steps, self.batch, self.cfg, self.traffic)
        self.ids = self.host["sparse"]["token"]
        mark = self._phase("make_batches", mark)
        self._build_program()
        mark = self._phase("build_program_and_state", mark)
        self._first_dispatch(mark)
        mark = time.perf_counter()
        for _ in range(int(self.traffic["warm_dispatches"])):
            self._dispatch()
        self._phase("warm_dispatches", mark)

    def reference_summary(self, precision: str = "f32", fault: str = "") -> Dict:
        f = self.ref_feed
        if "idx" not in f:
            f["idx"] = np.searchsorted(f["uniq"], self.ids).astype(np.int32)
        out = jax.device_get(self.ref.follow(
            self.seed, self.cfg, self.chips, f["ids"], f["idx"], self.host["label"], f["masks"],
            weight=self.host["dense"], precision=precision, fault=fault))
        if precision == "f32" and not fault and getattr(self, "ctx", None) is not None:
            self.ctx["ref_pairs_per_layer"] = float(np.mean(out["pairs_held"]))
        return ref_summary(out, self._grad_floor())


def open_session(**kw) -> Session:
    kw["cfg"] = train_scan_lm_keywords.with_row(kw["cfg"])
    ref = importlib.import_module("benchmark.reference." + kw["cfg"]["family"])
    return Session(reference=ref, **kw)
