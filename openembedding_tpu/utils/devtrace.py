"""Device time per stage: from a profiler trace (`*.xplane.pb`) and the
program's `trace.scope` names to seconds per scope on every device.

Reached as `trace.scope_map` / `trace.device_report` and through
`tools/trace_report.py --xplane DIR`. Two stages, so that the second is
checked against a small recorded trace (`tests/fixtures/`) with nothing but
Python:

1. `load_events(path)`: the xplane's device planes (`/device:TPU:n`, lines
   "XLA Ops" and "Async XLA Ops") as `[name, start_ns, dur_ns]` lists, and the
   host plane's annotations: `oetpu.*` (every `trace.span`), whatever else the
   host tracer recorded, and the `StepTraceAnnotation`s (events with a
   `step_num` stat).
2. `reduce_events(events, ...)`: per device busy and idle, time per scope,
   the unscoped remainder with its largest ops, `scoped_share`, op classes,
   and the longest idle gaps named by the host annotation over each.

Where an op's scope comes from. On the v5e runtime of this installation
(jaxlib 0.9.0, libtpu 0.0.34) an "XLA Ops" event carries three stats
(`device_offset_ps`, `device_duration_ps`, `Time Scale Multiplier`) and none
holds the `op_name` path; the event's NAME is the whole HLO instruction
(`%fusion.147 = f32[...] fusion(...), kind=kCustom, calls=...`, metadata
left out). So the join goes through `scope_map(compiled.as_text())`: the
instruction's name -> the scopes in its `metadata={op_name="..."}`. Two
conditions on that text. It is the text of the ONE program the trace ran
(two programs' instruction names collide). And it was compiled by this build:
JAX's persistent compilation cache leaves metadata out of its key
(`jax_compilation_cache_include_metadata_in_key`), so an executable loaded
from it carries the names of whichever build wrote the entry — none at all if
that build predates the scopes. `chip_smoke.py --profile` compiles its text
past the cache for that reason (`fresh_hlo_text`).

An instruction with no scope in its own metadata (the compiler made it, or
it sits in a loop body or a branch whose ops lost their `op_name`) is named
by what consumes it and by what calls its computation: `scope_map`'s two
rules.

A scope is a `<layer>.<stage>` token of the vocabulary's layers (`LAYERS`) found in
the path, through `jvp(...)`/`transpose(...)` wrappers. Scopes nest
(`exchange.owner_apply/sparse.apply`): an op is keyed by its whole chain
(`path_s`), by its innermost token (`scope_s`: "the apply, wherever it runs")
and by its outermost (`rollup_s`: "everything the owner does"). A fusion
carries its hero's metadata, so an op fused across a scope boundary counts
under its hero's scope.

The reduction follows `benchmark/trace_reduce.py` so that the two agree:
control flow that only contains other ops (while, conditional, call) is left
out; busy is the union of all op intervals; collectives, sync or async, are
counted once (`class_s["collective"]` is their union). Every busy moment is
put down to ONE scope — that of the op on the "XLA Ops" line then, or of the
async op in flight where none runs — so scope sums + unscoped = busy exactly.

This module imports nothing of the package (the benchmark may copy it).
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re
from typing import Dict, Iterable, List, Mapping, Optional, Tuple

LAYERS = ("sparse", "exchange", "dense", "trainer", "ssm", "attn", "moe", "lm",
          "mlp", "mtp", "kda", "cca", "router", "loop", "pack", "bd")
CONTAINERS = {"while", "conditional", "call"}
COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "collective-broadcast",
               "ragged-all-to-all")
UNSCOPED = ""

_SCOPE = re.compile(r"(?<![\w.])((?:%s)\.[a-z][a-z0-9_]*)(?![\w.])"
                    % "|".join(LAYERS))
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_CALLED = re.compile(
    r"\b(?:body|condition|to_apply|calls|true_computation|false_computation)"
    r"=%?([\w.\-]+)|\bbranch_computations=\{([^}]*)\}")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def scope_path(op_name: str) -> str:
    """`jit(f)/while/body/exchange.owner_apply/transpose(jvp(sparse.apply))/
    mul` -> `exchange.owner_apply/sparse.apply` ("" when no scope is in it;
    a scope re-entered by a callee counts once)."""
    out: List[str] = []
    for token in _SCOPE.findall(op_name or ""):
        if not out or out[-1] != token:
            out.append(token)
    return "/".join(out)


def scope_map(compiled_or_hlo_text) -> Dict[str, str]:
    """{instruction name: scope path} for EVERY instruction of a compiled
    program (`jitted.lower(...).compile()`, or its `.as_text()`): the
    outermost-to-innermost `trace.scope` tokens of the instruction's
    `op_name`, joined by "/"; "" for an instruction no scope covers. The
    innermost scope is `path.rsplit("/", 1)[-1]`.

    Two rules beyond the metadata. An instruction that HAS a scope keeps it
    under both.

    The consumers. An instruction the compiler made itself (it carries no
    `op_name` at all: a layout `copy`, the two halves of an async copy or
    slice, a hoisted convert or mask, the 26 `dynamic-update-slice` fusions
    that stack a routed layer's blocks) or a `copy` with no scope of its own
    takes the scope of the instructions that consume it, where they agree,
    through chains of such instructions of any length: the table-sized copies
    in front of `sparse.pack` and `sparse.unpack` exist because of those
    stages. A `tuple` the compiler made, while it has no scope, is no
    consumer: it carries a value out of a computation, or into a loop, and
    works on nothing.

    The call graph. The text is a list of computations (`%name (...) -> ...
    {` ... `}`), and an instruction may call some (`body=`, `condition=`,
    `to_apply=`, `calls=`, `branch_computations={...}`, `true_computation=`,
    `false_computation=`). An instruction with no scope of its own takes the
    scope path of the instruction that calls its computation, through nested
    calls: the TPU compiler runs a batched product over gathered weights as a
    `while` over slices whose body's ops carry no `op_name`, and the `while`
    carries the product's. A `while`, `conditional` or `call` the compiler
    made itself first takes the scope its consumers AND its producers agree
    on. A computation called from two scopes gives its instructions none.

    The consumers come first, because what consumes an instruction says more
    of it than where it stands (a conditional of the routed layer is called
    under `dense.tower` and nothing narrower; the blocks stacked inside it
    are consumed by `moe.experts`), and once more after the call graph, for
    the chains that end in an instruction the call graph named."""
    text = compiled_or_hlo_text
    if not isinstance(text, str):
        text = text.as_text()
    out: Dict[str, str] = {}
    users: Dict[str, List[str]] = {}
    producers: Dict[str, List[str]] = {}  # of the containers alone
    inside: Dict[str, List[str]] = {}   # computation -> its instructions
    callers: Dict[str, List[str]] = {}  # computation -> who calls it
    home: Dict[str, str] = {}           # instruction -> its computation
    made, made_callers, carriers = [], [], set()
    computation = ""
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            header = _COMPUTATION.match(line)
            if header is not None:
                computation = header.group(1)
            continue
        name = m.group(1)
        meta = _OP_NAME.search(line)
        out[name] = scope_path(meta.group(1)) if meta else UNSCOPED
        home[name] = computation
        inside.setdefault(computation, []).append(name)
        body = line[m.end():].split(", metadata=", 1)[0]
        called = [c.strip().lstrip("%")
                  for one, several in _CALLED.findall(body)
                  for c in ([one] if one else several.split(","))]
        for c in called:
            callers.setdefault(c, []).append(name)
        operands = [o for o in _OPERAND.findall(body) if o not in called]
        for operand in operands:
            users.setdefault(operand, []).append(name)
        opcode = opcode_of(line)
        if out[name] == UNSCOPED and (meta is None or opcode == "copy"):
            if opcode in CONTAINERS:
                made_callers.append(name)
                producers[name] = operands
            else:
                made.append(name)
            if opcode == "tuple":
                carriers.add(name)

    def agreed(names) -> str:
        paths = {out.get(n, UNSCOPED) for n in names
                 if not (n in carriers and out[n] == UNSCOPED)}
        return paths.pop() if len(paths) == 1 else UNSCOPED

    def consumers():
        left, before = [n for n in made if out[n] == UNSCOPED], None
        while len(left) != before:  # until a pass names nothing more
            for name in left:
                out[name] = agreed(users.get(name, []))
            left, before = [n for n in left if out[n] == UNSCOPED], len(left)

    consumers()
    for name in made_callers:
        out[name] = agreed(users.get(name, []) + producers[name])
    _inherit(out, inside, callers, home)
    consumers()
    return out


def _inherit(out: Dict[str, str], inside, callers, home) -> None:
    """`scope_map`'s call-graph rule: every scopeless instruction of a called
    computation takes the path its computation is called under."""
    under: Dict[str, str] = {}

    def path_of(computation: str) -> str:
        if computation not in under:
            under[computation] = UNSCOPED  # (HLO has no recursion: a guard)
            paths = {out[c] or path_of(home[c])
                     for c in callers.get(computation, [])}
            under[computation] = paths.pop() if len(paths) == 1 else UNSCOPED
        return under[computation]

    for computation, names in inside.items():
        path = path_of(computation)
        if path:
            for name in names:
                if out[name] == UNSCOPED:
                    out[name] = path


def opcode_of(instruction: str) -> str:
    """The opcode of an HLO instruction's text (`%x = f32[2] add(...)` ->
    `add`; "" when the text is no instruction)."""
    _, _, rest = instruction.partition(" = ")
    m = _OPCODE.search(" " + rest) if rest else None
    return m.group(1) if m else ""


@functools.lru_cache(maxsize=8192)  # a trace repeats a scan's few hundred ops
def _parse(text: str) -> Tuple[str, str, str, str]:
    """An op event's (instruction name, opcode, class, label). Classes as
    `benchmark/trace_reduce.classify`: `collective`, `dot` (convolution or
    dot, bare or as a fusion's hero: `kind=kOutput` is how the TPU compiler
    marks a convolution fused with its epilogue), `other`."""
    name, _, rest = text.partition(" = ")
    name = name.strip().lstrip("%")
    if not rest:
        return name, "", "other", name
    opcode = opcode_of(text)
    word = opcode + " " + name
    if any(c in word for c in COLLECTIVES):
        cls = "collective"
    elif ("convolution" in word or opcode == "dot"
          or re.search(r"\bdot\b", name)
          or (opcode == "fusion" and "kind=kOutput" in rest)):
        cls = "dot"
    else:
        cls = "other"
    shape = _SHAPE.search(rest)
    label = name if shape is None else \
        f"{name} {opcode} {shape.group(1)}[{shape.group(2)}]"
    return name, opcode, cls, label


# -- stage 1 ------------------------------------------------------------------

def find_xplane(xplane_dir: str) -> str:
    if os.path.isfile(xplane_dir):
        return xplane_dir
    found = sorted(glob.glob(os.path.join(xplane_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {xplane_dir}")
    return found[-1]


def load_events(path: str) -> Dict:
    """-> {"devices": {plane: {"ops": [...], "async": [...]}}, "host": [...],
    "steps": [...]}; every event `[name, start_ns, dur_ns]`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out: Dict = {"devices": {}, "host": [], "steps": []}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev: Dict[str, List] = {"ops": [], "async": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "Async XLA Ops": "async"}.get(line.name)
                if key is None:
                    continue
                dev[key] = [[e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns <= 0:
                        continue
                    ev = [e.name, float(e.start_ns), float(e.duration_ns)]
                    out["host"].append(ev)
                    if any(k == "step_num" for k, _ in e.stats):
                        out["steps"].append(ev)
    return out


# -- stage 2 ------------------------------------------------------------------

def _union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def _length(merged) -> float:
    return sum(b - a for a, b in merged)


def _uncovered(span: Tuple[float, float], cover, ends):
    """The pieces of `span` outside the merged, sorted union `cover` (`ends`:
    its interval ends, to start by bisection in a long union)."""
    s, e = span
    out, cur = [], s
    for j in range(bisect.bisect_right(ends, s), len(cover)):
        a, b = cover[j]
        if a >= e:
            break
        if a > cur:
            out.append((cur, a))
        cur = max(cur, b)
    if cur < e:
        out.append((cur, e))
    return out


def _minus(a, b) -> float:
    """Length of union `a` not covered by union `b` (both merged, sorted)."""
    ends = [y for _, y in b]
    return sum(y - x for span in a for x, y in _uncovered(span, b, ends))


def reduce_device(dev: Dict, scopes: Mapping[str, str]) -> Dict:
    """One device's events -> nanoseconds: busy, per scope path, unscoped ops,
    classes. `scopes`: {instruction name: scope path} (`scope_map`)."""
    path_ns: Dict[str, float] = {}
    unscoped: Dict[str, float] = {}
    classes = {"collective": 0.0, "dot": 0.0, "other": 0.0}
    coll, compute, ops = [], [], []

    def charge(ev, ns):
        name, _, _, label = _parse(ev[0])
        path = scopes.get(name, UNSCOPED)
        path_ns[path] = path_ns.get(path, 0.0) + ns
        if path == UNSCOPED:
            unscoped[label] = unscoped.get(label, 0.0) + ns

    for ev in sorted(dev["ops"], key=lambda e: e[1]):
        _, opcode, cls, _ = _parse(ev[0])
        if opcode in CONTAINERS:
            continue
        span = (ev[1], ev[1] + ev[2])
        ops.append((span, ev))
        if cls == "collective":
            coll.append(span)
        else:
            compute.append(span)
            classes[cls] += ev[2]
    ops_u = _union(span for span, _ in ops)
    # the "XLA Ops" line runs one op at a time; should two ever overlap, the
    # later one is charged only what the earlier ones left uncovered
    done = 0.0
    for span, ev in ops:
        a = max(span[0], done)
        if span[1] > a:
            charge(ev, span[1] - a)
            done = span[1]
    # an async op is charged where nothing on the ops line runs and no
    # earlier async op was charged
    ends = [b for _, b in ops_u]
    asyncs, done = [], 0.0
    for ev in sorted(dev["async"], key=lambda e: e[1]):
        span = (ev[1], ev[1] + ev[2])
        asyncs.append(span)
        if _parse(ev[0])[2] == "collective":
            coll.append(span)
        a = max(span[0], done)
        if span[1] > a:
            for x, y in _uncovered((a, span[1]), ops_u, ends):
                charge(ev, y - x)
            done = span[1]
    busy_u = _union(ops_u + asyncs)
    coll_u, comp_u = _union(coll), _union(compute)
    classes["collective"] = _length(coll_u)
    return {"busy_ns": _length(busy_u), "busy": busy_u,
            "first_ns": busy_u[0][0] if busy_u else 0.0,
            "last_ns": busy_u[-1][1] if busy_u else 0.0,
            "path_ns": path_ns, "unscoped_ns": unscoped, "class_ns": classes,
            "exposed_collective_ns": _minus(coll_u, comp_u)}


def _gaps(busy, host, top: int):
    """The longest idle gaps between ops, each named by the `oetpu.*`
    annotation that overlaps it most, or by any annotation where none of the
    program's does (`unattributed` where the host plane is silent)."""
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(busy, busy[1:])), reverse=True)[:top]
    out = []
    for length, s, e in gaps:
        best = {True: ("", 0.0), False: ("unattributed", 0.0)}
        for name, hs, hd in host:
            c = min(e, hs + hd) - max(s, hs)
            ours = name.startswith("oetpu.")
            if c > best[ours][1]:
                best[ours] = (name, c)
        out.append([best[True][0] or best[False][0], length / 1e9])
    return out


def _fold(path_ns: Mapping[str, float], pick) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for path, ns in path_ns.items():
        if path != UNSCOPED:
            key = pick(path.split("/"))
            out[key] = out.get(key, 0.0) + ns / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def reduce_events(events: Dict, *, scopes: Optional[Mapping[str, str]] = None,
                  steps: Optional[int] = None) -> Dict:
    """-> the report `device_report` returns (seconds). `steps`: the train
    steps inside the trace; where not given, the number of
    `StepTraceAnnotation`s recorded (per-step figures are left out when that
    is 0 too)."""
    scopes = scopes or {}
    if steps is None:
        steps = len(events.get("steps", [])) or None
    devices = {}
    for name, dev in sorted(events["devices"].items()):
        d = reduce_device(dev, scopes)
        if not d["busy"]:
            continue
        busy_s = d["busy_ns"] / 1e9
        span_s = (d["last_ns"] - d["first_ns"]) / 1e9
        unscoped_s = d["path_ns"].get(UNSCOPED, 0.0) / 1e9
        rep = {
            "busy_s": busy_s, "span_s": span_s, "idle_s": span_s - busy_s,
            "path_s": {p: ns / 1e9 for p, ns in sorted(
                d["path_ns"].items(), key=lambda kv: -kv[1]) if p != UNSCOPED},
            "scope_s": _fold(d["path_ns"], lambda toks: toks[-1]),
            "rollup_s": _fold(d["path_ns"], lambda toks: toks[0]),
            "unscoped_s": unscoped_s,
            "unscoped_top": [[k, v / 1e9] for k, v in sorted(
                d["unscoped_ns"].items(), key=lambda kv: -kv[1])[:5]],
            "scoped_share": (busy_s - unscoped_s) / busy_s,
            "class_s": {c: ns / 1e9 for c, ns in d["class_ns"].items()},
            "exposed_collective_s": d["exposed_collective_ns"] / 1e9,
            "idle_gaps": _gaps(d["busy"], events.get("host", []), 5),
        }
        if steps:
            rep["scope_ms_per_step"] = {k: v / steps * 1e3
                                        for k, v in rep["scope_s"].items()}
            rep["unscoped_ms_per_step"] = unscoped_s / steps * 1e3
            rep["step_ms"] = span_s / steps * 1e3
        devices[name] = rep
    return {"steps": steps, "devices": devices}


def device_report(xplane_dir: str, *, steps: Optional[int] = None,
                  scopes: Optional[Mapping[str, str]] = None) -> Dict:
    """Reduce the newest `*.xplane.pb` under `xplane_dir` (module doc): for
    every device busy, idle, seconds per scope (`scope_s` innermost,
    `rollup_s` outermost, `path_s` the whole chain), the unscoped remainder
    with its five largest ops, `scoped_share`, per-step figures where `steps`
    or the trace's `StepTraceAnnotation`s give a count, and the longest idle
    gaps by host annotation. `scopes`: `scope_map` of the compiled program(s)
    that ran; without it every op is unscoped, since this runtime's events
    carry no `op_name` (module doc)."""
    return reduce_events(load_events(find_xplane(xplane_dir)),
                         scopes=scopes, steps=steps)
