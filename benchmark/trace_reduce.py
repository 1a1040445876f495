"""From a profiler trace (`*.xplane.pb`) to the numbers the per-layer readers use.

Two stages, so that the second is checked against a small recorded trace
(`fixtures/`) with nothing but Python:

1. `load_events(path)`: the xplane's device planes (`/device:TPU:n`, lines
   "XLA Ops" and "Async XLA Ops") and the host's `TraceAnnotation`s, as plain
   lists `[name, start_ns, dur_ns]`.
2. `reduce_events(events, ...)`: busy and idle, op classes, collectives and their
   exposed part, top ops, and the longest idle gaps named by what the host did.

On this runtime an op event's name is its whole HLO instruction
(`%fusion.147 = f32[33554432,20]{...} fusion(...), kind=kCustom, calls=...`), so
classes come from the instruction's own opcode and name, never from the
program's scopes. Classes: `collective` (all-to-all, all-reduce, all-gather,
reduce-scatter, collective-permute, sync or async), `dot` (convolution or dot,
bare or as the hero of a fusion: `convolution` in its name, or `kind=kOutput`,
which is how the TPU compiler marks a convolution fused with its epilogue), and
`other`: everything else, a remainder on
purpose. Control flow that only contains other ops (while, conditional, call)
is left out of every sum.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

CONTAINERS = {"while", "conditional", "call"}
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "collective-broadcast", "ragged-all-to-all")
MOVES = {"copy", "copy-start", "copy-done", "reshape", "transpose", "bitcast",
         "dynamic-slice", "dynamic-update-slice", "slice", "concatenate", "pad"}
HOST_SPANS = ("dispatch_scan", "fence_loss")

_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def parse_op(text: str) -> Dict:
    """An HLO instruction's name, opcode, fusion kind, output and operand shapes."""
    name, _, rest = text.partition(" = ")
    if not rest:  # not an instruction: keep it whole, class `other`
        return {"name": text.strip("% "), "opcode": "", "kind": "", "out": [], "operands": []}
    m = _OPCODE.search(" " + rest)
    opcode = m.group(1) if m else ""
    head = rest[:m.start()] if m else rest
    tail = rest[m.end() - 1:] if m else ""
    kind = re.search(r"kind=(k[A-Za-z]+)", tail)
    return {"name": name.strip().lstrip("%"), "opcode": opcode,
            "kind": kind.group(1) if kind else "",
            "out": _SHAPE.findall(head), "operands": _SHAPE.findall(tail.split("), ")[0])}


def _elems(shape: Tuple[str, str]) -> int:
    n = 1
    for d in shape[1].split(","):
        if d:
            n *= int(d)
    return n


def classify(op: Dict) -> Tuple[str, str]:
    """-> (class, finer kind for the breakdown's names)."""
    word = op["opcode"] + " " + op["name"]
    if any(c in word for c in COLLECTIVES):
        return "collective", "collective"
    if ("convolution" in word or op["opcode"] == "dot" or re.search(r"\bdot\b", op["name"])
            or (op["opcode"] == "fusion" and op["kind"] == "kOutput")):
        return "dot", "dot"  # on the TPU a kOutput fusion is a convolution with its epilogue
    if op["opcode"] == "sort":
        return "other", "sort"
    if op["opcode"] in ("gather", "scatter"):
        return "other", op["opcode"]
    if op["opcode"] in MOVES:
        return "other", "data_movement"
    if op["opcode"] == "fusion" and op["kind"] == "kCustom" and op["out"] and op["operands"]:
        out = max(_elems(s) for s in op["out"])
        big = max(_elems(s) for s in op["operands"])
        if big >= 64 * out:
            return "other", "gather"
        if out == big and min(_elems(s) for s in op["operands"]) * 64 <= out:
            return "other", "scatter"
    return "other", "other"


def label(op: Dict, kind: str) -> str:
    shape = "_".join([op["out"][0][0]] + [d for d in op["out"][0][1].split(",") if d]) if op["out"] else ""
    return f"{op['name']}__{kind}__{shape}_"


# -- stage 1 ------------------------------------------------------------------

def load_events(path: str) -> Dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = {"ops": [], "async": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "Async XLA Ops": "async"}.get(line.name)
                if key:
                    dev[key] = [[e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        out["host"].append([e.name, float(e.start_ns), float(e.duration_ns)])
    return out


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not found:
        raise RuntimeError(f"no xplane under {trace_dir}")
    return found[-1]


# -- stage 2 ------------------------------------------------------------------

def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _length(merged) -> float:
    return sum(b - a for a, b in merged)


def _minus(a, b) -> float:
    """Length of union `a` not covered by union `b` (both merged and sorted)."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def reduce_device(dev: Dict) -> Dict:
    classes = {"collective": 0.0, "dot": 0.0, "other": 0.0}
    per_op: Dict[str, float] = {}
    busy, coll, compute = [], [], []
    for text, start, dur in dev["ops"]:
        op = parse_op(text)
        if op["opcode"] in CONTAINERS:
            continue
        cls, kind = classify(op)
        span = (start, start + dur)
        busy.append(span)
        per_op[label(op, kind)] = per_op.get(label(op, kind), 0.0) + dur
        if cls == "collective":
            coll.append(span)
        else:
            compute.append(span)
            classes[cls] += dur
    for text, start, dur in dev["async"]:
        span = (start, start + dur)
        busy.append(span)
        if classify(parse_op(text))[0] == "collective":
            coll.append(span)
    busy_u, coll_u, comp_u = _union(busy), _union(coll), _union(compute)
    classes["collective"] = _length(coll_u)  # sync ops and async spans, counted once
    return {"busy_ns": _length(busy_u), "busy": busy_u,
            "first_ns": busy_u[0][0] if busy_u else 0.0, "last_ns": busy_u[-1][1] if busy_u else 0.0,
            "class_ns": classes, "exposed_collective_ns": _minus(coll_u, comp_u), "per_op_ns": per_op}


def _gaps(busy, host, top: int):
    """The longest idle gaps between ops, each named by the host span that
    overlaps it most (or `unattributed`)."""
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])), reverse=True)[:top]
    out = []
    for length, s, e in gaps:
        best, cover = "unattributed", 0.0
        for name, hs, hd in host:
            c = min(e, hs + hd) - max(s, hs)
            if c > cover:
                best, cover = name, c
        out.append([best, length / 1e9])
    return out


def reduce_events(events: Dict, *, chips: int, window_s: float = None) -> Dict:
    """`window_s`: the traced window by the host's clock; where it is not given,
    the span from the first op to the last on any device."""
    devs = {name: reduce_device(d) for name, d in sorted(events["devices"].items())}
    devs = {n: d for n, d in devs.items() if d["busy"]}
    if len(devs) < chips:
        raise RuntimeError(f"trace has ops on {len(devs)} devices, the cell uses {chips}")
    span_s = (max(d["last_ns"] for d in devs.values()) - min(d["first_ns"] for d in devs.values())) / 1e9
    window = window_s if window_s is not None else span_s
    worst = max(devs.values(), key=lambda d: d["last_ns"] - d["first_ns"])
    least_busy = min(devs.values(), key=lambda d: d["busy_ns"])
    top = {}
    for d in devs.values():  # per op, the device on which it took longest
        for k, v in d["per_op_ns"].items():
            top[k] = max(top.get(k, 0.0), v)
    return {
        "devices": len(devs),
        "window_s": window,
        "busy_s": sum(d["busy_ns"] for d in devs.values()) / len(devs) / 1e9,
        "busy_worst_s": least_busy["busy_ns"] / 1e9,
        "span_s": (worst["last_ns"] - worst["first_ns"]) / 1e9,
        "class_s": {c: max(d["class_ns"][c] for d in devs.values()) / 1e9
                    for c in ("collective", "dot", "other")},
        "exposed_collective_s": max(d["exposed_collective_ns"] for d in devs.values()) / 1e9,
        "top_ops": [[k, v / 1e9] for k, v in sorted(top.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": _gaps(least_busy["busy"], events["host"], 5),
    }


def reduce_dir(trace_dir: str, *, chips: int, window_s: float = None) -> Dict:
    return reduce_events(load_events(find_xplane(trace_dir)), chips=chips, window_s=window_s)
