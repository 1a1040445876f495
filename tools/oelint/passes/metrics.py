"""metrics pass: metric-name hygiene at observe()/vtimer()/span() call sites.

The fifth oelint pass. Rules:

- metric names are dot-joined lowercase `group.name[.qualifier]` segments of
  `[a-z0-9_]+` (utils/metrics.py naming scheme); timer/span call sites pass
  group and name as separate lowercase segments;
- the GROUP (first name segment / the group argument of vtimer/span) is a
  closed registry (KNOWN_GROUPS) — a new group is a conscious act, not a
  typo minting `skwe.hot_id` silently;
- per-instance dimensions (table/shard/model) belong in labels, never
  embedded in a NAME segment (`pull.user_table.ms` reads like a conforming
  name; the INSTANCE_DIM rule rejects it mechanically).

Scans literal string arguments only (f-strings and variables pass through —
they are composed FROM checked literals). Inline suppression:
`# oelint: disable=metrics -- <reason>`.
"""

from __future__ import annotations

import re
from typing import List

from ..core import Finding, SourceFile

NAME = "metrics"
DIRS = ("openembedding_tpu", "examples", "tools")
SKIP = ("tools/oelint",)

NAME_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)+$")
SEGMENT = re.compile(r"^[a-z0-9_]+$")

# the metric-group registry: every observe() name's first segment and every
# vtimer()/span() group must be one of these (utils/metrics.py doc scheme)
KNOWN_GROUPS = {
    "dense",      # ZeRO dense-state sharding (MeshTrainer(dense_shard=True))
    "exchange",   # sharded-exchange wire costs + per-shard load/skew gauges
    "fleet",      # /fleetz cross-node scrape health
    "guard",      # runtime invariant guards (utils/guards.py fingerprints)
    "health",     # numerics sentinel (grad norms, non-finite counts, ef/quant error)
    "hot",        # replicated hot-row cache (MeshTrainer(hot_rows=...))
    "ingest",     # line-rate input path (data/ingest.py feed ring + parse pool)
    "lint",       # oelint's own run health (pass wall times, finding counts)
    "capsule",    # postmortem capsule emission health (utils/capsule.py)
    "history",    # metric history rings (utils/history.py /historz surface)
    "memory",     # device-memory ledger + preflight gate (utils/memwatch.py)
    "metrics",    # the metrics subsystem's own health (report_errors)
    "offload",    # host-cached table cache admission/flush/staging pipeline
    "pack",       # documents packed into a sequence: traced reset sites,
                  # documents a sequence, the longest one's share (PR 44)
    "persist",    # async/incremental persistence
    "placement",  # self-driving placement controller + cold-tail migration
    "serving",    # REST predict/pull/batching
    "skew",       # heavy-hitter sketches (utils/sketch.py)
    "slo",        # SLO engine verdicts/evaluation health (utils/slo.py)
    "sync",       # online model sync
    "train",      # example-loop wall timers
    "trainer",    # train-step phases + per-table pull stats
    "weave",      # oeweave deterministic-interleaving runs (tools/oeweave)
}

# per-instance dimensions embedded in a NAME segment instead of a label:
# a specific instance (`shard3`, `table_12`) or a smuggled instance name
# (`user_table`). Generic uses (`shard_rows`, `bucket_fill`) stay legal.
INSTANCE_DIM = re.compile(
    r"^(?:(?:table|shard|model|instance)_?\d+"
    r"|[a-z0-9_]+_(?:table|shard|model|instance))$")

# the label-KEY registry: every literal key in a labels={...} dict at an
# observe()/vtimer()/span() site must be one of these. Label keys are
# series DIMENSIONS — each new key multiplies registry cardinality (and
# history-ring count) across every value it ever takes, so an unbounded
# dimension (request_id, step, a raw feature value) is a memory leak with a
# metrics API. A new key is a conscious act, like a new group.
KNOWN_LABELS = {
    "component",  # memory ledger component (utils/memwatch.py)
    "fn",         # traced trainer entry point (bounded enum: train_step /
                  # train_many — `trainer.traces`)
    "form",       # how a scan holds a packed table (bounded enum: lines /
                  # rows — `sparse.packed_tables`)
    "hop",        # sync lineage hop (bounded enum: commit/publish/fetch/
                  # apply/swap/serve — sync/lineage.py HOP_ORDER)
    "instance",   # fleet-merge node id (metrics.merge_prometheus)
    "kind",       # operation kind within a group (bounded enum)
    "model",      # serving model sign
    "pass",       # oelint pass name (bounded by the pass registry)
    "path",       # which body a traced attention core's shape allows (bounded
                  # enum: fused / blockwise — `attn.cores`)
    "pool",       # parse-pool instance label (data/ingest.py)
    "rank",       # hot-row popularity rank bucket (utils/sketch.py)
    "ring",       # feed-ring instance label (data/ingest.py)
    "shard",      # table shard ordinal (bounded by mesh size)
    "site",       # which function of a packed sequence was given document
                  # starts (bounded enum: ssd / conv / attn — `pack.resets`)
    "slo",        # SLO spec name (bounded by the spec file)
    "slot",       # optimizer slot name (bounded enum)
    "table",      # embedding table / variable name
}

# labels={...} dict literals near a metrics call site; keys checked against
# KNOWN_LABELS. Only LITERAL keys are checkable — a computed key passes
# through here, but composes from a dict some other literal site built.
LABELS_DICT = re.compile(r"""labels\s*=\s*\{(?P<body>[^{}]*)\}""")
LABEL_KEY = re.compile(r"""(["'])(?P<key>[^"']+)\1\s*:""")

# observe("metric.name", ...) — metrics.observe or bare observe
OBSERVE = re.compile(r"""(?<![\w.])(?:metrics\.|M\.)?observe\(\s*
                         (["'])(?P<name>[^"']+)\1""", re.VERBOSE)
# vtimer("group", "name") / trace.span("group", "name") / span("group", ...)
TIMER = re.compile(r"""(?<![\w.])(?:metrics\.|M\.|trace\.|_trace\.)?
                       (?:vtimer|span)\(\s*
                       (["'])(?P<group>[^"']+)\1\s*,\s*
                       (["'])(?P<name>[^"']+)\3""", re.VERBOSE)


def lint_text(sf: SourceFile) -> List[Finding]:
    text = sf.text
    bad: List[Finding] = []

    def flag(pos: int, message: str) -> None:
        line = text.count("\n", 0, pos) + 1
        if not sf.suppressed(line, NAME):
            bad.append(Finding(sf.rel, line, NAME, message))

    for m in OBSERVE.finditer(text):
        name = m.group("name")
        if not NAME_RE.fullmatch(name):
            flag(m.start(), f"observe({name!r}) — metric names are "
                 "dot-joined lowercase group.name segments")
            continue
        segments = name.split(".")
        if segments[0] not in KNOWN_GROUPS:
            flag(m.start(), f"observe({name!r}) — unknown metric group "
                 f"{segments[0]!r}; register it in "
                 "tools/oelint/passes/metrics.py KNOWN_GROUPS")
        for seg in segments:
            if INSTANCE_DIM.fullmatch(seg):
                flag(m.start(), f"observe({name!r}) — segment {seg!r} "
                     "embeds a per-instance dimension (table/shard/model) "
                     "in the NAME; put it in labels={...} instead")
    for m in TIMER.finditer(text):
        for part in (m.group("group"), m.group("name")):
            if not SEGMENT.fullmatch(part):
                flag(m.start(), f"timer/span segment {part!r} — group and "
                     "name are single lowercase [a-z0-9_]+ segments")
            elif INSTANCE_DIM.fullmatch(part):
                flag(m.start(), f"timer/span segment {part!r} — embeds a "
                     "per-instance dimension (table/shard/model); use "
                     "labels={...}")
        group = m.group("group")
        if SEGMENT.fullmatch(group) and group not in KNOWN_GROUPS:
            flag(m.start(), f"span/vtimer group {group!r} — unknown metric "
                 "group; register it in tools/oelint/passes/metrics.py "
                 "KNOWN_GROUPS")
    for m in LABELS_DICT.finditer(text):
        for km in LABEL_KEY.finditer(m.group("body")):
            key = km.group("key")
            if key not in KNOWN_LABELS:
                flag(m.start(), f"label key {key!r} — unknown label "
                     "dimension; every label key multiplies series "
                     "cardinality, so the set is a closed registry "
                     "(tools/oelint/passes/metrics.py KNOWN_LABELS)")
    return bad


def _lint_slo_specs(root: str) -> List[Finding]:
    """Checked-in SLO spec files (tools/**/*slo*.json) must reference metric
    names in the `group.name` scheme with a registered group — a spec with a
    typo'd metric would otherwise sit at UNKNOWN forever, and an unregistered
    group means the metric can never be emitted by linted code."""
    import glob
    import json
    import os
    findings: List[Finding] = []
    pattern = os.path.join(root, "tools", "**", "*slo*.json")
    for path in sorted(glob.glob(pattern, recursive=True)):
        rel = os.path.relpath(path, root)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError) as e:
            findings.append(Finding(rel, 1, NAME,
                                    f"unparseable SLO spec file: {e}"))
            continue
        if not isinstance(doc, list):
            findings.append(Finding(rel, 1, NAME,
                                    "SLO spec file must be a JSON list of "
                                    "spec objects"))
            continue
        for i, d in enumerate(doc):
            where = f"spec #{i} ({d.get('name', '?')})" \
                if isinstance(d, dict) else f"spec #{i}"
            if not isinstance(d, dict) or "metric" not in d:
                findings.append(Finding(rel, 1, NAME,
                                        f"{where}: not a spec object with a "
                                        "'metric' field"))
                continue
            metric = str(d["metric"])
            if not NAME_RE.fullmatch(metric):
                findings.append(Finding(
                    rel, 1, NAME, f"{where}: metric {metric!r} — metric "
                    "names are dot-joined lowercase group.name segments"))
                continue
            segments = metric.split(".")
            if segments[0] not in KNOWN_GROUPS:
                findings.append(Finding(
                    rel, 1, NAME, f"{where}: metric {metric!r} — unknown "
                    f"group {segments[0]!r}; register it in "
                    "tools/oelint/passes/metrics.py KNOWN_GROUPS"))
            for seg in segments:
                if INSTANCE_DIM.fullmatch(seg):
                    findings.append(Finding(
                        rel, 1, NAME, f"{where}: metric {metric!r} — "
                        f"segment {seg!r} embeds a per-instance dimension; "
                        "SLO specs pin instances with 'labels'"))
    return findings


def run(files: List[SourceFile], root: str) -> List[Finding]:
    findings: List[Finding] = []
    for sf in files:
        findings.extend(lint_text(sf))
    findings.extend(_lint_slo_specs(root))
    return sorted(findings, key=lambda f: (f.path, f.line))
