"""hlo-budget pass: pin the collective set of every key entry-point config.

The scattered per-test HLO pins (tests/test_dedup.py, test_wire.py,
test_hot.py each count all-to-alls for one path) generalize here: this pass
COMPILES the train step for every key configuration on the 8-virtual-device
CPU mesh, counts collectives by kind in the optimized HLO, records the
static wire-bytes model, and compares against the checked-in budget
(`tools/oelint/hlo_budget.json`). A PR that adds a collective (or grows the
wire) to a pinned path fails `make lint` with a human-readable diff instead
of silently costing every future step.

Configurations (the acceptance matrix): the fused dim-group exchange,
hot-row cache on/off, and all three wire formats —
collective counts AND `exchange.wire_bytes_per_step` are pinned per config.

Regenerate after an intentional change:

    make lint-budget            # python -m tools.oelint --update-budget

and commit the diff — the json IS the review surface for collective changes.
Runs CPU-only (`JAX_PLATFORMS=cpu`, 8 virtual devices); no chip needed.
"""

from __future__ import annotations

import hashlib
import json
import os
import re
import threading
from typing import Dict, List, Optional, Tuple

from ..core import Finding, iter_py_files

NAME = "hlo-budget"
DIRS = ()  # compiles programs; scans no source files
BUDGET_REL = "tools/oelint/hlo_budget.json"
# measured-counts cache keyed on a source digest: warm `make lint` skips all
# ten XLA compiles (see measure_cached). Local state, gitignored.
CACHE_REL = "tools/oelint/.hlo_measure_cache.json"

# --changed-only reruns this pass only when these paths changed (anything
# else cannot alter the compiled collective set)
TRIGGERS = (
    "openembedding_tpu/parallel/", "openembedding_tpu/ops/",
    "openembedding_tpu/model.py", "openembedding_tpu/embedding.py",
    "openembedding_tpu/optimizers.py", "openembedding_tpu/tables/",
    "tools/oelint/",
)

COLLECTIVES = {
    "all_to_all": r" all-to-all(?:-start)?\(",
    "all_reduce": r" all-reduce(?:-start)?\(",
    "all_gather": r" all-gather(?:-start)?\(",
    "reduce_scatter": r" reduce-scatter(?:-start)?\(",
    "collective_permute": r" collective-permute(?:-start)?\(",
}

# result-buffer tensor types on a collective's definition line, e.g.
# `%all-to-all.1 = s8[8,56,16]{2,1,0} all-to-all(...)`
_TYPE_RE = re.compile(
    r"(pred|bf16|f16|f32|f64|s8|u8|s16|u16|s32|u32|s64|u64)\[([0-9,]*)\]")
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s16": 2,
             "u16": 2, "f32": 4, "s32": 4, "u32": 4, "f64": 8, "s64": 8,
             "u64": 8}

# the acceptance matrix: wire formats, hot on/off, and full placement (hot
# cache + cold-tail migration directory) — the `fused_fp32_placement`
# steady-state step must pin the IDENTICAL
# exchange-collective set as `fused_fp32_hot` (3 a2a, 0 all-gather, same
# wire bytes): the owner-assignment indirection is pure local math (two
# extra hash probes riding the fused sort), never a wire collective. The
# only delta is +4 scalar all-reduces — the `mig_unique`/`mig_hits` stats
# riding the existing per-key stats psum (2 stats x 2 tables).
CONFIGS = (
    {"name": "fused_fp32", "wire": "fp32", "hot_rows": 0},
    {"name": "fused_bf16", "wire": "bf16", "hot_rows": 0},
    {"name": "fused_int8", "wire": "int8", "hot_rows": 0},
    {"name": "fused_fp32_hot", "wire": "fp32", "hot_rows": 32},
    {"name": "fused_fp32_placement", "wire": "fp32",
     "hot_rows": 32, "mig_rows": 32},
    # round-13 in-collective configs: the compiled a2a operands must carry
    # the narrow dtype — `forbid_a2a_dtypes` turns a silent fall-back to
    # fp32-through-the-a2a into a lint failure even when the budget matches
    # (a fresh --update-budget would otherwise just pin the regression).
    # fused_int8_inband also runs error feedback (the default for int8) and
    # the two-stage s8 hot reduce; fused_fp32_hot_int8 isolates the hot
    # reduce's format from the exchange's.
    {"name": "fused_bf16_inband", "wire": "bf16",
     "hot_rows": 32, "forbid_a2a_dtypes": ("f32",)},
    {"name": "fused_int8_inband", "wire": "int8",
     "hot_rows": 32, "forbid_a2a_dtypes": ("f32", "bf16", "u16")},
    {"name": "fused_fp32_hot_int8", "wire": "fp32",
     "hot_rows": 32, "hot_wire": "int8"},
    # round-14 ZeRO dense sharding: the sharded dense update must cost
    # EXACTLY one reduce-scatter + one all-gather over the flat dense state
    # (bytes pinned below) and must not perturb the exchange collectives —
    # same a2a set and wire bytes as fused_fp32.
    {"name": "fused_fp32_zero", "wire": "fp32",
     "hot_rows": 0, "dense_shard": True},
    # round-16 numerics sentinel: the health stats ride the step's stats
    # psum — the pinned contract is that sentinel=True costs ONLY a handful
    # of extra SCALAR all-reduces (one per health stat key) and changes the
    # exchange a2a set and wire bytes by exactly zero vs fused_fp32 (and
    # every sentinel-off config above stays byte-identical, delta 0).
    {"name": "fused_fp32_sentinel", "wire": "fp32",
     "hot_rows": 0, "sentinel": True},
    # round-17 per-table wire: the one dim-8 group splits on (dim, fmt) into
    # TWO fused a2a groups (6 a2as, not 3) and the compiled payloads must
    # carry BOTH formats — `require_a2a_dtypes` fails the lint when either
    # side silently falls back (f32 gone = table "a" got quantized, s8 gone
    # = table "b" fell back to fp32), budget-independently.
    {"name": "fused_mixed_wire", "wire": {"a": "fp32", "b": "int8"},
     "hot_rows": 0, "require_a2a_dtypes": ("f32", "s8")},
    # round-17 quantized dense ZeRO collectives: dense_wire="int8" replaces
    # the fp32 reduce-scatter with an s8 in-band a2a + per-replica fp32 sum
    # and ships the params all_gather on the u16 bf16 carrier. `pins` holds
    # hlo_reduce_scatter_bytes at EXACTLY 0 budget-independently (a silent
    # fall-back to the fp32 reduce_scatter fails `make lint` even straight
    # after --update-budget), and the s8 requirement pins the encoded grad
    # a2a itself.
    {"name": "fused_fp32_zero_int8", "wire": "fp32",
     "hot_rows": 0, "dense_shard": True, "dense_wire": "int8",
     "require_a2a_dtypes": ("s8",),
     "pins": {"hlo_reduce_scatter_bytes": 0}},
    # round-23 density-adaptive sparse dense collectives: dense_wire=
    # "sparse_topk" ships each destination's top-k gradient entries as s8
    # values + in-band scales + bitcast-s8 index lanes through the same
    # encoded a2a slot the int8 path uses (reduce-scatter stays at exactly
    # 0, pinned), with dense_stats=True riding the per-key stats psum (one
    # extra scalar lane — the measured density that drives the crossover).
    # The unattributed pin proves the sparse scatter-sum decode stays local:
    # GSPMD must not insert resharding around the index-lane plumbing.
    {"name": "fused_fp32_zero_sparse", "wire": "fp32", "hot_rows": 0,
     "dense_shard": True, "dense_wire": "sparse_topk", "dense_stats": True,
     "require_a2a_dtypes": ("s8",),
     "pins": {"hlo_reduce_scatter_bytes": 0,
              "unattributed_collectives": 0}},
    # round-18 software-pipelined train_many: the K-step window compiles a
    # scan whose body prefetches batch t+1's exchange BEFORE batch t's dense
    # compute/apply. fused_fp32_many is the serial K-step window on the same
    # model so the pipelined delta is a reviewable json diff: pipelining may
    # add ONLY the conflict-patch collectives (wire_conflict_patch_bytes —
    # the exact-replay re-gather of rows batch t updated) on top of the
    # serial set — zero hidden wire beyond the patch. The unattributed pin
    # is update-proof: GSPMD must not insert resharding into the rotated
    # carry plumbing.
    {"name": "fused_fp32_many", "wire": "fp32",
     "hot_rows": 0, "train_many": 4},
    {"name": "fused_fp32_pipelined", "wire": "fp32",
     "hot_rows": 0, "train_many": 4, "pipeline_steps": True,
     "pins": {"unattributed_collectives": 0}},
)


def _ensure_cpu() -> None:
    """8 virtual CPU devices, never an accelerator — same contract as the
    root conftest.py; must run before jax initializes a backend."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")
    # pin the id-key layout the budget compiles under: x64 ON is the repo's
    # test-suite convention (63-bit hashed id spaces need int64 keys —
    # tests/conftest.py), and the budget must measure ONE fixed world
    jax.config.update("jax_enable_x64", True)


def count_collectives(hlo_text: str) -> Dict[str, int]:
    return {kind: len(re.findall(pat, hlo_text))
            for kind, pat in COLLECTIVES.items()}


# -- implicit-reshard attribution (consumed by the implicit-reshard pass) ----
#
# Every collective the PROTOCOL asks for is traced from an explicit lax call,
# and XLA stamps those ops with `metadata={op_name="jit(...)/.../psum"}` —
# the op_name tail is the traced primitive. GSPMD-INSERTED collectives
# (resharding between mismatched in/out shardings) carry no such traced-op
# tail: that absence is the detection signal for the silent-all-gather class.
_OPNAME_RE = re.compile(r'op_name="([^"]+)"')
# (a `trace.scope` lengthens the path in the MIDDLE —
# `.../shard_map/trainer.metrics/psum` — the tail stays the primitive.)
# The set is this JAX's collective primitives (`jax._src.lax.parallel`):
# under shard_map's varying-axes check a psum lowers as `psum_invariant`.
_EXPLICIT_TAILS = {
    "psum", "psum2", "pmean", "pmax", "pmin", "ppermute", "pbroadcast",
    "all_to_all", "all_gather", "all_gather_invariant", "reduce_scatter",
    "psum_scatter", "psum_invariant", "all_gather_reduced", "unreduced_psum",
    "unreduced_reduce_scatter", "ragged_all_to_all", "pgather",
}


def unattributed_collectives(hlo_text: str) -> List[Tuple[str, str]]:
    """[(kind, attribution)] for compiled collectives that do NOT trace back
    to an explicit collective primitive — i.e. GSPMD inserted them."""
    out: List[Tuple[str, str]] = []
    for line in hlo_text.splitlines():
        for kind, pat in COLLECTIVES.items():
            if not re.search(pat, line):
                continue
            m = _OPNAME_RE.search(line)
            tail = m.group(1).rsplit("/", 1)[-1] if m else ""
            base = tail.split("[", 1)[0]
            if base not in _EXPLICIT_TAILS:
                out.append((kind, m.group(1) if m else "<no metadata>"))
            break
    return out


def collective_payloads(hlo_text: str,
                        kinds=("all_to_all", "all_gather")):
    """[(kind, dtype, result_bytes)] per matching collective in the compiled
    HLO — one entry per tensor in the op's RESULT type (tuple results
    contribute one entry each). This is the measured counterpart of
    `ops/wire.exchange_cost`, which prices exactly these result buffers."""
    out = []
    for line in hlo_text.splitlines():
        for kind in kinds:
            m = re.search(COLLECTIVES[kind], line)
            if not m:
                continue
            head = line[:m.start()]
            eq = head.find("= ")
            if eq < 0:
                continue
            for dt, dims in _TYPE_RE.findall(head[eq + 2:]):
                n = 1
                for d in dims.split(","):
                    if d.strip():
                        n *= int(d)
                out.append((kind, dt, n * _ITEMSIZE[dt]))
            break
    return out


def _budget_model():
    """The smallest model that exercises every pinned path: two dim-8 tables
    (array + hash) in ONE dim-group, duplicate-heavy planted batch — the
    same shape family the HLO pin tests use."""
    import numpy as np

    import flax.linen as nn
    import jax.numpy as jnp

    import openembedding_tpu as embed
    from openembedding_tpu.model import EmbeddingModel

    class Tower(nn.Module):
        @nn.compact
        def __call__(self, embedded, dense):
            bias = self.param("bias", nn.initializers.zeros, (1,),
                              jnp.float32)
            out = (jnp.sum(embedded["a"].astype(jnp.float32), axis=(1, 2))
                   + jnp.sum(embedded["b"].astype(jnp.float32), axis=(1, 2)))
            return out + bias[0]

    model = EmbeddingModel(Tower(), [
        embed.Embedding(256, 8, name="a"),
        embed.Embedding(-1, 8, name="b", capacity=4096),
    ])
    rng = np.random.default_rng(0)
    B = 64
    a = rng.integers(0, 256, (B, 4)).astype(np.int32)
    # hash ids < 2^31: the x64-off truncation warning is model.py's to give,
    # not lint noise (collective counts are id-range-invariant)
    b = rng.integers(0, 1 << 20, (B, 3)).astype(np.int64)
    a[:, 0] = np.array([7, 13])[rng.integers(0, 2, B)]
    batch = {"sparse": {"a": a, "b": b},
             "label": rng.integers(0, 2, (B,)).astype(np.float32)}
    return model, batch


def make_trainer(config: Dict):
    """Budget trainer for one config (also the corpus tests' hook — they
    measure deliberately violated variants through the same plumbing)."""
    _ensure_cpu()
    import openembedding_tpu as embed
    from openembedding_tpu.parallel import MeshTrainer, make_mesh

    model, batch = _budget_model()
    wire = config["wire"]
    if isinstance(wire, dict):
        wire = dict(wire)  # MeshTrainer keeps the per-table dict as-is
    trainer = MeshTrainer(
        model, embed.Adagrad(learning_rate=0.1), mesh=make_mesh(),
        wire=wire, hot_rows=config["hot_rows"],
        mig_rows=config.get("mig_rows", 0),
        hot_wire=config.get("hot_wire"),
        dense_shard=config.get("dense_shard", False),
        dense_wire=config.get("dense_wire"),
        dense_topk=config.get("dense_topk"),
        dense_stats=config.get("dense_stats", False),
        sentinel=config.get("sentinel", False),
        pipeline_steps=config.get("pipeline_steps", False))
    return trainer, batch


def measure_trainer(trainer, batch, *, train_many: int = 0) -> Dict[str, int]:
    """Compile the train step, count collectives, record the static wire
    model (`exchange.wire_bytes_per_step` from `trainer.last_wire_cost`)
    AND the measured truth: per-collective payload bytes/dtypes read off
    the compiled HLO, plus `wire_model_delta` = measured minus modeled a2a
    bytes (0 == the cost model prices the compiled program exactly).

    `train_many=K` compiles the K-step `jit_train_many` window instead of
    the single step (the round-18 pipelined-scan configs): counts are then
    per compiled MODULE — the scan body's collectives appear once however
    many iterations run — with the prologue/epilogue instances on top, so
    a serial-window baseline config is what makes the numbers comparable."""
    state = trainer.init(batch)
    if train_many:
        import jax as _jax
        import numpy as _np
        stacked = _jax.tree_util.tree_map(
            lambda x: _np.stack([_np.asarray(x)] * int(train_many)), batch)
        fn = trainer.jit_train_many(stacked, state)
        text = fn.lower(state, stacked).compile().as_text()
    else:
        step = trainer.jit_train_step(batch, state)
        text = step.lower(state, batch).compile().as_text()
    counts = count_collectives(text)
    cost = trainer.last_wire_cost or {}
    counts["wire_bytes_per_step"] = int(cost.get("bytes_per_step", 0))
    if "conflict_patch_bytes" in cost:
        # pipelined configs only: the ONLY wire the pipeline may add, plus
        # the modeled bytes it moves off the critical path
        counts["wire_conflict_patch_bytes"] = int(
            cost["conflict_patch_bytes"])
        counts["wire_overlapped_bytes"] = int(
            cost.get("overlapped_bytes", 0))
    pay = collective_payloads(
        text, kinds=("all_to_all", "all_gather", "reduce_scatter"))
    a2a = [(d, b) for k, d, b in pay if k == "all_to_all"]
    ag = [(d, b) for k, d, b in pay if k == "all_gather"]
    rs = [(d, b) for k, d, b in pay if k == "reduce_scatter"]
    counts["hlo_a2a_bytes"] = sum(b for _, b in a2a)
    counts["hlo_all_gather_bytes"] = sum(b for _, b in ag)
    # ZeRO dense sharding's reduce-scatter (result = the 1/S local chunk)
    counts["hlo_reduce_scatter_bytes"] = sum(b for _, b in rs)
    counts["hlo_a2a_dtypes"] = ",".join(sorted({d for d, _ in a2a}))
    model_a2a = (int(cost.get("bytes_per_step", 0))
                 + int(cost.get("hot_a2a_bytes", 0))
                 + int(cost.get("dense_a2a_bytes", 0)))
    counts["wire_model_delta"] = counts["hlo_a2a_bytes"] - model_a2a
    # GSPMD-inserted collectives (no traced-op attribution). The count is a
    # pinned budget key (0 everywhere); the "_"-prefixed detail is carried
    # for the implicit-reshard pass's message and skipped by compare().
    unattr = unattributed_collectives(text)
    counts["unattributed_collectives"] = len(unattr)
    counts["_unattributed_detail"] = "; ".join(
        f"{kind} <- {attr}" for kind, attr in unattr)
    return counts


def measure(configs=CONFIGS) -> Dict[str, Dict[str, int]]:
    out: Dict[str, Dict[str, int]] = {}
    for cfg in configs:
        trainer, batch = make_trainer(cfg)
        out[cfg["name"]] = measure_trainer(
            trainer, batch, train_many=cfg.get("train_many", 0))
    return out


# -- source-digest compile cache ---------------------------------------------
#
# The ten config compiles dominate `make lint` wall time (~minutes cold).
# Nothing outside the package source (plus this pass and the jax build) can
# change what they compile to, so measured counts are cached keyed on a
# digest of exactly those inputs; a warm `make lint` replays the cached
# counts and still runs compare()/forbidden_dtype_findings()/the
# implicit-reshard check against the CURRENT budget json.

_MEASURE_LOCK = threading.Lock()
_MEASURE_MEMO: Dict[str, Dict[str, Dict]] = {}


def source_digest(root: str) -> str:
    h = hashlib.sha256()
    try:
        import jax
        h.update(jax.__version__.encode())
    except Exception:  # noqa: BLE001 — no jax == cache never hits anyway
        pass
    rels = list(iter_py_files(root, ("openembedding_tpu",)))
    rels.append("tools/oelint/passes/hlo_budget.py")
    for rel in sorted(rels):
        h.update(rel.encode())
        try:
            with open(os.path.join(root, rel), "rb") as f:
                h.update(hashlib.sha256(f.read()).digest())
        except OSError:
            h.update(b"<unreadable>")
    h.update(repr(CONFIGS).encode())
    return h.hexdigest()


def measure_cached(root: str, *, force: bool = False) -> Dict[str, Dict]:
    """measure() with the digest cache in front. Thread-safe: the hlo-budget
    and implicit-reshard passes run concurrently and share one compile."""
    with _MEASURE_LOCK:
        digest = source_digest(root)
        if not force:
            if digest in _MEASURE_MEMO:
                return _MEASURE_MEMO[digest]
            path = os.path.join(root, CACHE_REL)
            try:
                with open(path, encoding="utf-8") as f:
                    doc = json.load(f)
                if doc.get("digest") == digest:
                    _MEASURE_MEMO[digest] = doc["measured"]
                    return doc["measured"]
            except (OSError, ValueError, KeyError):
                pass
        measured = measure()
        _MEASURE_MEMO[digest] = measured
        tmp = os.path.join(root, CACHE_REL) + f".tmp.{os.getpid()}"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump({"digest": digest, "measured": measured}, f,
                          indent=2, sort_keys=True)
                f.write("\n")
            os.replace(tmp, os.path.join(root, CACHE_REL))
        except OSError:
            pass
        return measured


def load_budget(root: str) -> Optional[Dict]:
    path = os.path.join(root, BUDGET_REL)
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def compare(measured: Dict[str, Dict[str, int]],
            budget: Optional[Dict]) -> List[Finding]:
    """Human-readable diff of measured collective counts vs the checked-in
    budget; empty list == pinned paths unchanged."""
    out: List[Finding] = []
    if budget is None or "configs" not in budget:
        return [Finding(BUDGET_REL, 1, NAME,
                        "no checked-in HLO budget; generate one with "
                        "`python -m tools.oelint --update-budget` and "
                        "commit it")]
    pinned = budget["configs"]
    for name, counts in sorted(measured.items()):
        if name not in pinned:
            out.append(Finding(
                BUDGET_REL, 1, NAME,
                f"config {name!r} is not in the checked-in budget; "
                "run --update-budget and review the diff"))
            continue
        for kind in sorted(set(counts) | set(pinned[name])):
            if kind.startswith("_"):
                continue  # detail payloads ride along unpinned
            got_raw = counts.get(kind, 0)
            want_raw = pinned[name].get(kind, 0)
            if isinstance(got_raw, str) or isinstance(want_raw, str):
                # string-valued pins (hlo_a2a_dtypes): equality, not deltas
                if str(got_raw) == str(want_raw):
                    continue
                out.append(Finding(
                    BUDGET_REL, 1, NAME,
                    f"config {name!r}: {kind} changed "
                    f"{want_raw!r} -> {got_raw!r}. If intentional, "
                    "regenerate the budget (`python -m tools.oelint "
                    "--update-budget`) and commit the json diff; otherwise "
                    "a collective payload silently changed dtype"))
                continue
            got = int(got_raw)
            want = int(want_raw)
            if got == want:
                continue
            delta = got - want
            if kind == "wire_bytes_per_step":
                what = (f"per-device exchange bytes/step "
                        f"{'grew' if delta > 0 else 'shrank'} "
                        f"{want} -> {got} ({delta:+d})")
            elif kind in ("hlo_a2a_bytes", "hlo_all_gather_bytes",
                          "wire_model_delta"):
                what = (f"compiled-HLO {kind} "
                        f"{'grew' if delta > 0 else 'shrank'} "
                        f"{want} -> {got} ({delta:+d})")
            else:
                what = (f"{abs(delta)} {kind.replace('_', '-')} "
                        f"collective(s) {'ADDED to' if delta > 0 else 'removed from'} "
                        f"the compiled step ({want} -> {got})")
            out.append(Finding(
                BUDGET_REL, 1, NAME,
                f"config {name!r}: {what}. If intentional, regenerate the "
                "budget (`python -m tools.oelint --update-budget`) and "
                "commit the json diff; otherwise a collective/recompile "
                "crept onto a pinned path"))
    return out


def forbidden_dtype_findings(measured: Dict[str, Dict],
                             configs=CONFIGS) -> List[Finding]:
    """Budget-independent dtype policy: configs declaring
    `forbid_a2a_dtypes` fail when the compiled all-to-alls carry a forbidden
    payload dtype — a silent fp32 fall-back in a quantized wire mode is a
    lint failure even straight after --update-budget."""
    out: List[Finding] = []
    by_name = {c["name"]: c for c in configs}
    for name, counts in sorted(measured.items()):
        forbid = by_name.get(name, {}).get("forbid_a2a_dtypes", ())
        if not forbid:
            continue
        got = {d for d in
               str(counts.get("hlo_a2a_dtypes", "")).split(",") if d}
        bad = sorted(got & set(forbid))
        if bad:
            out.append(Finding(
                BUDGET_REL, 1, NAME,
                f"config {name!r}: compiled all-to-all payload dtype(s) "
                f"{', '.join(bad)} are forbidden for this wire mode — the "
                "quantized exchange fell back to a wide payload (measured "
                f"a2a dtypes: {counts.get('hlo_a2a_dtypes')!r})"))
    return out


def required_dtype_findings(measured: Dict[str, Dict],
                            configs=CONFIGS) -> List[Finding]:
    """Budget-independent inverse of `forbidden_dtype_findings`: configs
    declaring `require_a2a_dtypes` fail when any required payload dtype is
    MISSING from the compiled all-to-alls — a quantized path that silently
    widened (or a mixed-wire split that collapsed to one format) is a lint
    failure even straight after --update-budget."""
    out: List[Finding] = []
    by_name = {c["name"]: c for c in configs}
    for name, counts in sorted(measured.items()):
        require = by_name.get(name, {}).get("require_a2a_dtypes", ())
        if not require:
            continue
        got = {d for d in
               str(counts.get("hlo_a2a_dtypes", "")).split(",") if d}
        missing = sorted(set(require) - got)
        if missing:
            out.append(Finding(
                BUDGET_REL, 1, NAME,
                f"config {name!r}: compiled all-to-all payload dtype(s) "
                f"{', '.join(missing)} are REQUIRED for this wire mode but "
                "absent — a quantized path silently widened or a mixed-wire "
                "group collapsed to one format (measured a2a dtypes: "
                f"{counts.get('hlo_a2a_dtypes')!r})"))
    return out


def pinned_value_findings(measured: Dict[str, Dict],
                          configs=CONFIGS) -> List[Finding]:
    """Budget-independent exact-value pins: configs declaring `pins`
    ({counter: value}) fail when the measured counter differs — unlike the
    json budget, --update-budget cannot absorb a regression on these (e.g.
    dense_wire configs pin hlo_reduce_scatter_bytes at 0: any fp32
    reduce_scatter reappearing on the quantized dense path fails loud)."""
    out: List[Finding] = []
    by_name = {c["name"]: c for c in configs}
    for name, counts in sorted(measured.items()):
        pins = by_name.get(name, {}).get("pins", {})
        for key, want in sorted(pins.items()):
            got = counts.get(key, 0)
            if got != want:
                out.append(Finding(
                    BUDGET_REL, 1, NAME,
                    f"config {name!r}: {key} = {got} but this config PINS "
                    f"it at {want} (declared in hlo_budget.CONFIGS, not the "
                    "json budget — --update-budget cannot absorb this; the "
                    "compiled path regressed)"))
    return out


def update_budget(root: str) -> str:
    _ensure_cpu()
    import jax
    path = os.path.join(root, BUDGET_REL)
    measured = measure_cached(root, force=True)
    configs = {name: {k: v for k, v in counts.items()
                      if not k.startswith("_")}
               for name, counts in measured.items()}
    doc = {
        "_comment": "Pinned collective counts + static wire bytes per "
                    "compiled train-step config (tools/oelint/passes/"
                    "hlo_budget.py). Regenerate with `python -m "
                    "tools.oelint --update-budget`; the diff is the review "
                    "surface for collective changes.",
        "jax": jax.__version__,
        "mesh_devices": 8,
        "configs": configs,
    }
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def run(files, root: str) -> List[Finding]:
    measured = measure_cached(root)
    return (compare(measured, load_budget(root))
            + forbidden_dtype_findings(measured)
            + required_dtype_findings(measured)
            + pinned_value_findings(measured))
