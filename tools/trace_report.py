"""Turn `trace.dump_chrome()` dumps into a latency table or a stitched tree.

    python tools/trace_report.py /tmp/serving_trace.json
    python tools/trace_report.py /tmp/serving_trace.json --by name --sort p99
    python tools/trace_report.py http://127.0.0.1:8501/tracez
    python tools/trace_report.py sub_dump.json pub_dump.json --trace <rid>
    python tools/trace_report.py --xplane /tmp/prof/train [--steps 16]

Reads the Chrome-trace JSON the flight recorder exports (`utils/trace.py
dump_chrome`, serving `--trace-dump`, examples `--trace-dump`) — or, given
an `http(s)://` URL, fetches a RUNNING node's `GET /tracez` ring live, so an
operator can profile without a restart — aggregates the complete ("X")
events per span name (or per group/category with `--by group`) and prints
count / mean / p50 / p95 / p99 / max / total milliseconds — the offline twin
of the live `/metrics` histograms, with the advantage that it works on a
dump mailed from a production node.

`--trace <request_id>` switches to the STITCHED-TREE view: spans of that
trace are collected across every given dump (one per process — e.g. the
subscriber node's and the publisher node's), linked by their
process-qualified `span_uid`/`parent_uid` args and, ACROSS the HTTP
boundary, by `remote_parent` (the caller's span uid the callee's root span
recorded off the `X-OETPU-Trace` header), and printed as one indented
cross-process tree.

`--xplane DIR` reads a DEVICE profile instead (`jax.profiler.trace(DIR)`,
`chip_smoke.py --profile`): the newest `*.xplane.pb` under DIR, reduced by the
program's `trace.device_report` to, per device, busy and idle time, time per
`trace.scope` stage (innermost name, and rolled up by the outermost), the
unscoped remainder with its largest ops, `scoped_share`, and the longest idle
gaps named by the host annotation (`oetpu.<group>.<name>` = a `trace.span`)
over each. The ops' stages are joined in from every `*.hlo.txt` under DIR —
the compiled programs' text, which `chip_smoke.py --profile` writes there (or
`jitted.lower(...).compile().as_text()`); `--steps N` gives the train steps
inside the trace where it carries no `StepTraceAnnotation`.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import Dict, List


def _tracez_events(doc: dict) -> List[dict]:
    """A live `GET /tracez` body ({"spans": [...], "events": [...]},
    `Span.as_dict` shape) -> Chrome-trace "X" event dicts the aggregator
    already understands (ms -> us for `dur`)."""
    out = []
    for s in doc.get("spans", []):
        proc = s.get("process")
        args = {k: v for k, v in (("request_id", s.get("request_id")),
                                  ("span_id", s.get("span_id")),
                                  ("remote_parent", s.get("remote_parent")))
                if v is not None}
        if proc is not None and s.get("span_id") is not None:
            args["span_uid"] = f"{proc}:{s['span_id']}"
            if s.get("parent_id") is not None:
                args["parent_uid"] = f"{proc}:{s['parent_id']}"
        out.append({"ph": "X", "name": str(s.get("name", "?")),
                    "cat": str(s.get("group", "?")),
                    "ts": float(s.get("start") or 0.0) * 1e6,
                    "dur": float(s.get("duration_ms") or 0.0) * 1e3,
                    "args": args})
    return out


def load_events(path: str) -> List[dict]:
    """Chrome-trace dump path, or an `http(s)://` URL to a node (its
    `/tracez` is fetched — appended automatically when missing)."""
    if path.startswith(("http://", "https://")):
        import urllib.request
        url = path.rstrip("/")
        if not url.endswith("/tracez"):
            url = f"{url}/tracez"
        with urllib.request.urlopen(url, timeout=10.0) as r:
            return _tracez_events(json.loads(r.read().decode()))
    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc if isinstance(doc, list) else [])
    if not isinstance(events, list):
        raise ValueError(f"{path}: not a Chrome-trace dump "
                         "(no traceEvents array)")
    return events


def report(events: List[dict], by: str = "name") -> List[dict]:
    """-> rows [{key, count, mean_ms, p50_ms, p95_ms, p99_ms, max_ms,
    total_ms}], slowest p99 first. `by`: "name" (span name) or "group"
    (Chrome-trace category)."""
    import numpy as np

    if by not in ("name", "group"):
        raise ValueError(f"by={by!r}: expected 'name' or 'group'")
    field = "name" if by == "name" else "cat"
    groups: Dict[str, List[float]] = {}
    for ev in events:
        if ev.get("ph") != "X":
            continue
        key = str(ev.get(field, "?"))
        groups.setdefault(key, []).append(float(ev.get("dur", 0.0)) / 1e3)
    rows = []
    for key, durs in groups.items():
        d = np.asarray(durs)
        rows.append({"key": key, "count": int(d.size),
                     "mean_ms": float(d.mean()),
                     "p50_ms": float(np.percentile(d, 50)),
                     "p95_ms": float(np.percentile(d, 95)),
                     "p99_ms": float(np.percentile(d, 99)),
                     "max_ms": float(d.max()),
                     "total_ms": float(d.sum())})
    rows.sort(key=lambda r: r["p99_ms"], reverse=True)
    return rows


def format_table(rows: List[dict]) -> str:
    if not rows:
        return "(no complete spans in dump)"
    cols = ("count", "mean_ms", "p50_ms", "p95_ms", "p99_ms", "max_ms",
            "total_ms")
    width = max(len("span"), max(len(r["key"]) for r in rows))
    head = "span".ljust(width) + "".join(c.rjust(12) for c in cols)
    lines = [head, "-" * len(head)]
    for r in rows:
        cells = "".join(
            (f"{r[c]:d}" if c == "count" else f"{r[c]:.3f}").rjust(12)
            for c in cols)
        lines.append(r["key"].ljust(width) + cells)
    return "\n".join(lines)


def trace_tree(events: List[dict], request_id: str) -> List[str]:
    """One trace's spans across N processes' dumps as an indented tree.

    Spans link locally by `span_uid` -> `parent_uid` and across the HTTP
    boundary by `remote_parent` (both args `chrome_events` emits); a span
    whose parent is in no dump renders as a root. Siblings sort by start
    time. Lines carry the owning process id so the hop between processes is
    visible in the stitched rendering."""
    spans = [ev for ev in events
             if ev.get("ph") == "X"
             and (ev.get("args") or {}).get("request_id") == request_id
             and (ev.get("args") or {}).get("span_uid")]
    by_uid = {ev["args"]["span_uid"]: ev for ev in spans}
    children: Dict[str, List[dict]] = {}
    roots = []
    for ev in spans:
        a = ev["args"]
        parent = a.get("parent_uid") or a.get("remote_parent")
        if parent is not None and parent in by_uid:
            children.setdefault(parent, []).append(ev)
        else:
            roots.append(ev)
    lines: List[str] = []

    def emit(ev: dict, depth: int) -> None:
        a = ev["args"]
        proc = str(a.get("span_uid", ":")).split(":")[0]
        hop = " <-remote" if (a.get("remote_parent")
                              and not a.get("parent_uid")) else ""
        lines.append(f"{'  ' * depth}{ev.get('cat', '?')}.{ev['name']} "
                     f"[{proc}] {float(ev.get('dur', 0.0)) / 1e3:.3f}ms"
                     f"{hop}")
        for c in sorted(children.get(a["span_uid"], []),
                        key=lambda e: float(e.get("ts", 0.0))):
            emit(c, depth + 1)

    for r in sorted(roots, key=lambda e: float(e.get("ts", 0.0))):
        emit(r, 0)
    return lines


def format_device_report(rep: dict) -> str:
    """`trace.device_report`'s dict as text: one block per device."""
    steps = rep.get("steps")
    lines = []
    for name, d in rep["devices"].items():
        per = (lambda s: f"{s / steps * 1e3:10.4f}") if steps else \
            (lambda s: " " * 10)
        lines.append(
            f"{name}: busy {d['busy_s']:.4f} s, idle {d['idle_s']:.4f} s, "
            f"scoped_share {100 * d['scoped_share']:.2f}%"
            + (f", {steps} steps of {d['step_ms']:.4f} ms" if steps else ""))
        lines.append(f"  {'scope (innermost)':34s}{'seconds':>10s}"
                     f"{'ms/step' if steps else '':>10s}{'% busy':>8s}")
        for scope, s in d["scope_s"].items():
            lines.append(f"  {scope:34s}{s:10.4f}{per(s)}"
                         f"{100 * s / d['busy_s']:8.2f}")
        lines.append(f"  {'(unscoped)':34s}{d['unscoped_s']:10.4f}"
                     f"{per(d['unscoped_s'])}"
                     f"{100 * d['unscoped_s'] / d['busy_s']:8.2f}")
        for label, s in d["unscoped_top"]:
            lines.append(f"      {label[:60]:60s}{s:10.4f}")
        lines.append("  rolled up by outermost scope: " + ", ".join(
            f"{k} {v:.4f}" for k, v in d["rollup_s"].items()))
        lines.append("  classes: " + ", ".join(
            f"{k} {v:.4f}" for k, v in d["class_s"].items())
            + f", exposed collective {d['exposed_collective_s']:.4f}")
        lines.append("  longest idle gaps: " + (", ".join(
            f"{who} {s * 1e3:.3f} ms" for who, s in d["idle_gaps"])
            or "none"))
    return "\n".join(lines) if lines else "(no device ops in the trace)"


def xplane_report(xplane_dir: str, steps=None) -> dict:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from openembedding_tpu.utils import trace
    scopes: Dict[str, str] = {}
    for path in sorted(glob.glob(os.path.join(xplane_dir, "**", "*.hlo.txt"),
                                 recursive=True)):
        with open(path) as f:
            scopes.update(trace.scope_map(f.read()))
    return trace.device_report(xplane_dir, steps=steps, scopes=scopes)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="per-group latency table (or, with --trace, a stitched "
                    "cross-process span tree) from trace.dump_chrome() dumps")
    ap.add_argument("dump", nargs="*",
                    help="Chrome-trace JSON path(s), or live node "
                         "http(s)://host:port[/tracez] URL(s)")
    ap.add_argument("--by", choices=("name", "group"), default="name",
                    help="aggregate per span name (default) or per group")
    ap.add_argument("--sort", choices=("p50", "p95", "p99", "mean", "max",
                                       "total", "count"), default="p99",
                    help="sort column (descending)")
    ap.add_argument("--trace", default=None, metavar="REQUEST_ID",
                    help="render ONE trace as a stitched cross-process span "
                         "tree instead of the latency table")
    ap.add_argument("--xplane", default=None, metavar="DIR",
                    help="reduce the device profile under DIR to time per "
                         "trace.scope stage instead (module doc)")
    ap.add_argument("--steps", type=int, default=None,
                    help="with --xplane: train steps inside the trace")
    args = ap.parse_args(argv)
    if args.xplane is not None:
        print(format_device_report(xplane_report(args.xplane, args.steps)))
        return 0
    if not args.dump:
        ap.error("give a dump (or --xplane DIR)")
    events: List[dict] = []
    for path in args.dump:
        events.extend(load_events(path))
    if args.trace is not None:
        lines = trace_tree(events, args.trace)
        print("\n".join(lines) if lines
              else f"(no spans for trace {args.trace!r})")
        return 0
    rows = report(events, by=args.by)
    key = args.sort if args.sort == "count" else f"{args.sort}_ms"
    rows.sort(key=lambda r: r[key], reverse=True)
    print(format_table(rows))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
