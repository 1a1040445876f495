"""oelint: static-analysis + invariant-guard suite for this repo.

Eleven passes over `openembedding_tpu/` (see each module's doc):

- trace-hazard     — recompile/concretization hazards in jit-reachable code
- host-sync        — device→host sync discipline in `# oelint: hot-path` fns
- sharding         — one PartitionSpec spelling per logical placement leaf
- spmd-divergence  — per-process host control flow upstream of collectives
- hlo-budget       — per-config collective counts vs tools/oelint/hlo_budget.json
- implicit-reshard — no compiled collective without a traced-op attribution
- lockset          — `# guarded-by:` discipline + lock-ordering cycles
- atomicity        — check-then-act on guarded state split across the lock
- cond-wait        — Condition.wait predicate loops, notify under the lock
- thread-lifecycle — every stored/started thread has a reachable join
- metrics          — metric-name hygiene

Run them all with `make lint` / `python -m tools.oelint`; the runtime
counterpart (executable never-re-jit + collective-fingerprint assertions) is
`openembedding_tpu/utils/guards.py`.

Passes run CONCURRENTLY (the hlo-budget/implicit-reshard XLA compiles
release the GIL under the AST walks); the two compiling passes share one
measurement behind `hlo_budget.measure_cached`'s source-digest cache, so a
warm full run costs seconds, not minutes.
"""

from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, Iterable, List, Optional, Tuple

from .core import (Finding, SourceFile, changed_files, iter_py_files,
                   load_files, repo_root)
from .passes import ALL_PASSES, BY_NAME


def run_passes(pass_names: Optional[Iterable[str]] = None, *,
               root: Optional[str] = None,
               changed_only: bool = False,
               parallel: bool = True,
               ) -> Tuple[List[Finding], Dict[str, float]]:
    """Run the named passes (default: all) over the repo.

    Returns (findings, {pass name: seconds}). Suppressed findings are
    already filtered by each pass; bare (reasonless) suppressions in any
    scanned file surface as `suppression` findings.

    `changed_only` narrows file-scanning passes to files changed vs HEAD —
    except passes declaring `NEEDS_ALL_FILES` (cross-file registries /
    call graphs), which run on their full file set whenever ANY of their
    files changed — and runs the compiling passes (hlo-budget,
    implicit-reshard) only when one of their `TRIGGERS` paths changed.
    """
    root = root or repo_root()
    selected = [BY_NAME[n] for n in (pass_names or BY_NAME)]
    changed = changed_files(root) if changed_only else None

    findings: List[Finding] = []
    timings: Dict[str, float] = {}
    file_cache: Dict[str, SourceFile] = {}
    suppression_checked: set = set()
    tasks: List[Tuple[str, object, List[SourceFile]]] = []

    for p in selected:
        if not p.DIRS:  # compiling pass: no files, gated on TRIGGERS
            if changed is not None and not any(
                    rel.startswith(p.TRIGGERS) for rel in changed):
                timings[p.NAME] = 0.0
                continue
            tasks.append((p.NAME, p, []))
            continue
        rels = iter_py_files(root, p.DIRS, skip=getattr(p, "SKIP", ()))
        if changed is not None:
            if getattr(p, "NEEDS_ALL_FILES", False):
                # cross-file pass: all files, but only if one of them changed
                if not any(r in changed for r in rels):
                    timings[p.NAME] = 0.0
                    continue
            else:
                rels = [r for r in rels if r in changed]
        files = []
        for rel in rels:
            sf = file_cache.get(rel)
            if sf is None:
                sf = file_cache[rel] = SourceFile(root, rel)
                if sf.parse_error is not None:
                    findings.append(Finding(
                        rel, sf.parse_error.lineno or 1, "parse",
                        f"syntax error: {sf.parse_error.msg}"))
            if sf.tree is not None or p.NAME == "metrics":
                files.append(sf)
            if rel not in suppression_checked:
                suppression_checked.add(rel)
                findings.extend(sf.bare_suppressions())
        tasks.append((p.NAME, p, files))

    def _one(task):
        name, p, files = task
        t0 = time.monotonic()
        return name, p.run(files, root), time.monotonic() - t0

    if parallel and len(tasks) > 1:
        with ThreadPoolExecutor(max_workers=min(8, len(tasks))) as ex:
            results = list(ex.map(_one, tasks))
    else:
        results = [_one(t) for t in tasks]
    for name, fs, dt in results:
        findings.extend(fs)
        timings[name] = dt
    findings = sorted(set(findings),
                      key=lambda f: (f.path, f.line, f.pass_name, f.message))
    return findings, timings
