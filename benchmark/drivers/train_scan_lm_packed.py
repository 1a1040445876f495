"""Driver `train_scan_lm_packed`: `train_scan_lm_keywords`' session (the
program's construction from the configuration's `make_keywords`, the K-step
scan back to back, the fence, the staging, the sums for the comparison, all
inherited) over PACKED batches: every sequence is documents laid end to end,
and the batch carries, as its `dense` entry, 1 where a document begins (added
to the inherited batches before the program is built from them). What differs
is the batches alone, and what follows from them: the reference is
fed the starts beside the ids, and the readers are told how many (query, key)
pairs lie inside documents (`ref_pairs_per_layer`, free in a family with no
routed layer: `readers/family_step_mfu.py` hands it to the work module).

Document lengths are lognormal from the seed (the mix's `doc_length_median` /
`doc_length_sigma`), clipped to [`doc_length_min`, `doc_length_max`], the last
of a sequence cut at its end; position 0 always starts one.
"""

from __future__ import annotations

import importlib
import math
from typing import Dict

import jax
import numpy as np

from benchmark.drivers import train_scan_lm_keywords
from benchmark.drivers.train_scan import ref_summary


def document_starts(seed: int, sequences: int, seq: int, traffic: Dict) -> np.ndarray:
    """-> (sequences, seq) int32: 1 where a document begins."""
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32, 0xD0C5])
    lo, hi = int(traffic["doc_length_min"]), int(traffic["doc_length_max"])
    mu, sigma = math.log(traffic["doc_length_median"]), float(traffic["doc_length_sigma"])
    starts = np.zeros((sequences, seq), np.int32)
    for row in starts:
        at = 0
        while at < seq:
            row[at] = 1
            at += int(np.clip(round(rng.lognormal(mu, sigma)), lo, hi))
    return starts


def pairs_inside_documents(starts: np.ndarray) -> float:
    """The (query, key <= query) pairs of one document, summed over a
    (..., S) array of starts."""
    flat = starts.reshape(-1, starts.shape[-1])
    total = 0
    for row in flat:
        lengths = np.diff(np.append(np.flatnonzero(row), row.size)).astype(np.int64)
        total += int(np.sum(lengths * (lengths + 1) // 2))
    return float(total)


class Session(train_scan_lm_keywords.Session):
    def _build_program(self):
        """The batches `train_scan_tokens.Session.setup` has just made, with
        the documents' starts as their `dense` entry; then the program, its
        state and the staging as inherited."""
        seq = int(self.traffic["sequence_length"])
        starts = document_starts(self.seed, self.k_steps * self.batch, seq, self.traffic)
        self.host["dense"] = starts.reshape(self.k_steps, self.batch, seq)
        super()._build_program()

    def context(self) -> Dict:
        """The mean count a step of (query, key) pairs inside documents, from
        the starts this session drew, where the routed families put their pairs."""
        ctx = super().context()
        ctx["ref_pairs_per_layer"] = pairs_inside_documents(self.host["dense"]) / self.k_steps
        return ctx

    def reference_summary(self, precision: str = "f32", fault: str = "") -> Dict:
        f = self.ref_feed
        if "idx" not in f:
            f["idx"] = np.searchsorted(f["uniq"], self.ids).astype(np.int32)
        out = self.ref.follow(self.seed, self.cfg, self.chips, f["ids"], f["idx"], self.host["label"],
                              f["masks"], starts=self.host["dense"], precision=precision, fault=fault)
        return ref_summary(jax.device_get(out), self._grad_floor())


def open_session(**kw) -> Session:
    kw["cfg"] = train_scan_lm_keywords.with_row(kw["cfg"])
    ref = importlib.import_module("benchmark.reference." + kw["cfg"]["family"])
    return Session(reference=ref, **kw)
