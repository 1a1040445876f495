"""trace-hazard pass: recompile / concretization hazards in jit-reachable code.

The repo's hot paths live or die by the never-re-jit discipline: the sharded
exchange, the hot-row cache lifecycle and the trainer step all compile ONCE
and must keep running across refreshes, capacity changes and traffic drift
(`parallel/sharded.py` module doc, tests/test_hot.py). The hazards that break
it are all *Python-level* patterns invisible to the type checker:

- Python `if`/`while`/`assert` on a TRACED value — under jit this either
  raises ConcretizationTypeError or (via `int()`-style escapes) silently
  retraces per value;
- `int()` / `float()` / `bool()` on a tracer — the concretization escape
  hatch itself;
- data-dependent shapes: `jnp.nonzero`/`jnp.unique`/... without `size=`, or
  using their result's `.shape` as a Python value;
- unhashable (list/dict/set) or float literals fed to `static_argnums` /
  `static_argnames` positions — per-value recompiles or immediate TypeErrors;
- iterating a `set` while tracing — nondeterministic iteration order, so two
  runs of the same code can emit different programs (cache-buster).

Scope: functions REACHABLE from the jitted entry points. Roots are the
protocol functions below plus anything annotated `# oelint: jit-entry`;
reachability follows simple-name calls across the scanned files (method and
free-function calls alike). Library calls (jnp/jax/np) and GENERIC method
tails (`.get`, `.load`, `.items`, ...) are not followed — the latter collide
with half the stdlib and would drag host-only code into jit scope.

Taint: a value is considered traced when it (transitively) comes from a
jnp/jax array op, propagated in SOURCE ORDER through local assignments.
Attribute reads of `.shape`/`.ndim`/`.dtype`/`.size` are STATIC under jit
and never tainted — that is what keeps `if x.shape[0]:` legal and this pass
quiet on the real tree; `x is None` identity tests and known static
predicates (`is_pair`) are static too. Function parameters are NOT assumed
traced (the pass cannot know call sites), so a hazard on a raw parameter
needs a human; hazards on op RESULTS — the overwhelming majority — are
caught mechanically.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from ..core import Finding, JIT_ENTRY_RE, SourceFile

NAME = "trace-hazard"
DIRS = ("openembedding_tpu",)

# the jitted protocol entry points (parallel/sharded.py, model.py Trainer)
DEFAULT_ROOTS = {
    "grouped_lookup_train", "sharded_lookup", "grouped_apply_gradients",
    "hot_writeback", "hot_gather", "mig_writeback", "mig_gather",
    "train_step", "train_many", "eval_step",
}

# library roots whose calls SEED taint (array-producing ops)
_JAX_ROOTS = {"jnp", "jax", "lax"}
# ...except these tails, which return static Python values under jit
_STATIC_TAILS = {
    "axis_size", "ndim", "shape", "size", "dtype", "itemsize",
    "issubdtype", "result_type", "can_cast", "promote_types",
}
# repo predicates that only inspect dtype/shape — static under jit
_STATIC_PREDICATES = {"is_pair"}
# calls whose OUTPUT SHAPE is data-dependent: illegal under jit without
# `size=`, and their `.shape` is a trace hazard even outside jit
_DATA_DEP_TAILS = {"nonzero", "flatnonzero", "argwhere", "unique"}
# method tails too generic to follow in the call graph (dict.get, json.load,
# file.read, ... would alias half the repo into "jit-reachable")
_GENERIC_TAILS = {
    "get", "set", "load", "loads", "dump", "dumps", "save", "open", "close",
    "read", "write", "replace", "copy", "items", "keys", "values", "update",
    "pop", "append", "extend", "add", "remove", "discard", "join", "split",
    "strip", "format", "encode", "decode", "setdefault", "sort", "index",
    "count", "clear", "put", "wait", "start", "stop", "run", "next", "send",
}


def _attr_chain(node: ast.AST) -> Optional[List[str]]:
    """Attribute/Name chain as ["jax", "lax", "psum"]; None if not a chain."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return list(reversed(parts))
    return None


def _call_chain(call: ast.Call) -> Optional[List[str]]:
    return _attr_chain(call.func)


def _is_jaxish(call: ast.Call) -> bool:
    chain = _call_chain(call)
    if not chain or chain[0] not in _JAX_ROOTS:
        return False
    return chain[-1] not in _STATIC_TAILS


def _is_data_dep(call: ast.Call) -> bool:
    chain = _call_chain(call)
    if not chain or chain[0] not in _JAX_ROOTS:
        return False
    if chain[-1] not in _DATA_DEP_TAILS:
        return False
    return not any(kw.arg == "size" for kw in call.keywords)


def _is_set_expr(node: ast.AST) -> bool:
    if isinstance(node, (ast.Set, ast.SetComp)):
        return True
    if isinstance(node, ast.Call):
        chain = _call_chain(node)
        return chain is not None and len(chain) == 1 and \
            chain[0] in ("set", "frozenset")
    return False


class _FnInfo:
    def __init__(self, sf: SourceFile, node: ast.AST, qualname: str):
        self.sf = sf
        self.node = node
        self.qualname = qualname


def _index_functions(files: List[SourceFile]) -> Dict[str, List[_FnInfo]]:
    """name -> defs across all files (methods indexed by bare method name)."""
    index: Dict[str, List[_FnInfo]] = {}
    for sf in files:
        if sf.tree is None:
            continue
        stack: List[Tuple[ast.AST, str]] = [(sf.tree, "")]
        while stack:
            node, prefix = stack.pop()
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qual = f"{prefix}{child.name}"
                    index.setdefault(child.name, []).append(
                        _FnInfo(sf, child, qual))
                    stack.append((child, qual + "."))
                elif isinstance(child, ast.ClassDef):
                    stack.append((child, f"{prefix}{child.name}."))
    return index


def _called_names(fn: ast.AST) -> Set[str]:
    """Simple names this function calls, minus library and generic tails."""
    out: Set[str] = set()
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        chain = _call_chain(node)
        if chain is None:
            continue
        if chain[0] in _JAX_ROOTS or chain[0] == "np":
            continue
        if chain[-1] in _GENERIC_TAILS:
            continue
        out.add(chain[-1])
    return out


def _reachable(index: Dict[str, List[_FnInfo]],
               roots: Set[str]) -> List[_FnInfo]:
    seen: Set[int] = set()
    order: List[_FnInfo] = []
    work = [fi for name in sorted(roots) for fi in index.get(name, [])]
    while work:
        fi = work.pop()
        if id(fi.node) in seen:
            continue
        seen.add(id(fi.node))
        order.append(fi)
        for name in sorted(_called_names(fi.node)):
            for nxt in index.get(name, []):
                if id(nxt.node) not in seen:
                    work.append(nxt)
    return order


class _TaintChecker:
    """Source-order taint propagation + hazard checks for one function.
    Nested defs share the enclosing scope (a closure traced by the same
    jit). Single forward sweep: taint follows the order statements execute,
    so a later `jax.lax.scan` result never poisons an earlier static
    branch (loop-carried taint into a `while` test is re-checked once)."""

    def __init__(self, sf: SourceFile, fn: ast.AST, qualname: str):
        self.sf = sf
        self.fn = fn
        self.qualname = qualname
        self.tainted: Set[str] = set()
        self.data_dep: Set[str] = set()
        self.findings: List[Finding] = []
        self._flagged: Set[Tuple[int, str]] = set()

    # -- expression taint -----------------------------------------------------

    def is_tainted(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Call):
            chain = _call_chain(node)
            if chain and chain[-1] in _STATIC_PREDICATES:
                return False
            if _is_jaxish(node):
                return True
            # unknown call with a tainted argument: assume it flows through
            return any(self.is_tainted(a) for a in node.args) or \
                any(self.is_tainted(kw.value) for kw in node.keywords)
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_TAILS:
                return False  # .shape/.ndim/.dtype/... are static under jit
            return self.is_tainted(node.value)
        if isinstance(node, ast.Subscript):
            return self.is_tainted(node.value)
        if isinstance(node, ast.BinOp):
            return self.is_tainted(node.left) or self.is_tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.is_tainted(node.operand)
        if isinstance(node, ast.BoolOp):
            return any(self.is_tainted(v) for v in node.values)
        if isinstance(node, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False  # identity tests are static Python decisions
            return self.is_tainted(node.left) or \
                any(self.is_tainted(c) for c in node.comparators)
        if isinstance(node, ast.IfExp):
            return self.is_tainted(node.body) or self.is_tainted(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.is_tainted(e) for e in node.elts)
        if isinstance(node, ast.Starred):
            return self.is_tainted(node.value)
        return False

    def is_data_dep(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Call):
            return _is_data_dep(node)
        if isinstance(node, ast.Name):
            return node.id in self.data_dep
        if isinstance(node, ast.Subscript):
            return self.is_data_dep(node.value)
        return False

    # -- findings -------------------------------------------------------------

    def _flag(self, node: ast.AST, message: str) -> None:
        key = (node.lineno, message)
        if key in self._flagged or self.sf.suppressed(node.lineno, NAME):
            return
        self._flagged.add(key)
        self.findings.append(
            Finding(self.sf.rel, node.lineno, NAME,
                    f"{message} (in `{self.qualname}`, jit-reachable)"))

    def _assign_targets(self, target: ast.AST, value_tainted: bool,
                        value_data_dep: bool) -> None:
        if isinstance(target, ast.Name):
            if value_tainted:
                self.tainted.add(target.id)
            else:
                self.tainted.discard(target.id)
            if value_data_dep:
                self.data_dep.add(target.id)
            else:
                self.data_dep.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._assign_targets(elt, value_tainted, value_data_dep)

    # -- expression checks (R2/R3/ternary/set-comprehension) ------------------

    def _check_expr(self, expr: Optional[ast.AST]) -> None:
        if expr is None:
            return
        # comprehension targets first: their taint feeds the element exprs
        for sub in ast.walk(expr):
            if isinstance(sub, ast.comprehension):
                if self.is_tainted(sub.iter):
                    self._assign_targets(sub.target, True, False)
                if _is_set_expr(sub.iter):
                    self._flag(sub.iter,
                               "iterating a set while tracing: "
                               "nondeterministic iteration order feeds "
                               "nondeterministic trace order; sort it "
                               "(`sorted(...)`)")
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Call):
                chain = _call_chain(sub)
                if chain and len(chain) == 1 and \
                        chain[0] in ("int", "float", "bool") and sub.args \
                        and self.is_tainted(sub.args[0]):
                    self._flag(sub, f"`{chain[0]}()` on a traced value: "
                                    "forces a concretization/host sync and "
                                    "retraces per distinct value")
                elif _is_data_dep(sub):
                    self._flag(sub, f"`{'.'.join(chain)}` without `size=`: "
                                    "data-dependent output shape cannot "
                                    "trace under jit (and re-traces per "
                                    "shape when it can)")
            elif isinstance(sub, ast.IfExp) and self.is_tainted(sub.test):
                self._flag(sub, "ternary on a traced value: concretizes "
                                "the tracer; use jnp.where")
            elif isinstance(sub, ast.Attribute) and sub.attr == "shape" \
                    and self.is_data_dep(sub.value):
                self._flag(sub, ".shape of a data-dependent array "
                                "(nonzero/unique/...): the value is not "
                                "static under jit — carry an explicit "
                                "`size=` instead")

    # -- statement driver (source order) --------------------------------------

    def run(self) -> List[Finding]:
        for arg_default in getattr(self.fn.args, "defaults", []):
            self._check_expr(arg_default)
        self._process_body(self.fn.body)
        return self.findings

    def _process_body(self, body: List[ast.stmt]) -> None:
        for stmt in body:
            self._process_stmt(stmt)

    def _process_stmt(self, stmt: ast.stmt) -> None:
        if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            if stmt.value is not None:
                self._check_expr(stmt.value)
                t = self.is_tainted(stmt.value)
                d = self.is_data_dep(stmt.value)
                targets = (stmt.targets if isinstance(stmt, ast.Assign)
                           else [stmt.target])
                for tgt in targets:
                    self._check_expr(tgt)
                    self._assign_targets(tgt, t, d)
        elif isinstance(stmt, ast.If):
            self._check_expr(stmt.test)
            if self.is_tainted(stmt.test):
                self._flag(stmt, "Python `if` on a traced value: "
                                 "concretizes the tracer (error or "
                                 "per-value recompile); use jnp.where/"
                                 "lax.cond or hoist the decision to a "
                                 "static shape/config")
            self._process_body(stmt.body)
            self._process_body(stmt.orelse)
        elif isinstance(stmt, ast.While):
            self._check_while(stmt)
        elif isinstance(stmt, ast.For):
            self._check_expr(stmt.iter)
            if _is_set_expr(stmt.iter):
                self._flag(stmt.iter,
                           "iterating a set while tracing: nondeterministic "
                           "iteration order feeds nondeterministic trace "
                           "order; sort it (`sorted(...)`)")
            if self.is_tainted(stmt.iter):
                self._assign_targets(stmt.target, True, False)
            self._process_body(stmt.body)
            self._process_body(stmt.orelse)
        elif isinstance(stmt, ast.Assert):
            self._check_expr(stmt.test)
            if self.is_tainted(stmt.test):
                self._flag(stmt, "`assert` on a traced value: concretizes "
                                 "the tracer under jit; use checkify or a "
                                 "host-side check")
        elif isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                self._check_expr(item.context_expr)
            self._process_body(stmt.body)
        elif isinstance(stmt, ast.Try):
            self._process_body(stmt.body)
            for handler in stmt.handlers:
                self._process_body(handler.body)
            self._process_body(stmt.orelse)
            self._process_body(stmt.finalbody)
        elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            # nested def: traced by the same jit; shares the taint scope
            self._process_body(stmt.body)
        elif isinstance(stmt, (ast.Return, ast.Expr)):
            self._check_expr(stmt.value)
        elif isinstance(stmt, (ast.Raise,)):
            self._check_expr(stmt.exc)
        # remaining statement kinds carry no checkable expressions

    def _check_while(self, stmt: ast.While) -> None:
        self._check_expr(stmt.test)
        tainted_before = self.is_tainted(stmt.test)
        if tainted_before:
            self._flag(stmt, "Python `while` on a traced value: "
                             "concretizes the tracer; use lax.while_loop")
        self._process_body(stmt.body)
        if not tainted_before and self.is_tainted(stmt.test):
            # loop-carried taint: the test reads a name the body taints
            self._flag(stmt, "Python `while` on a traced value (tainted by "
                             "the loop body): concretizes the tracer; use "
                             "lax.while_loop")
        self._process_body(stmt.orelse)


# -- static-arg hashability (checked at every jit call site, not only the
# reachable set: a bad static arg breaks the caller wherever it lives) -------


def _static_positions(call: ast.Call) -> Tuple[Set[int], Set[str]]:
    """(static argnums, static argnames) declared on a jax.jit(...) call."""
    nums: Set[int] = set()
    names: Set[str] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnums":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, int):
                    nums.add(n.value)
        elif kw.arg == "static_argnames":
            for n in ast.walk(kw.value):
                if isinstance(n, ast.Constant) and isinstance(n.value, str):
                    names.add(n.value)
    return nums, names


def _bad_static_literal(node: ast.AST) -> Optional[str]:
    if isinstance(node, (ast.List, ast.ListComp)):
        return "list"
    if isinstance(node, (ast.Dict, ast.DictComp)):
        return "dict"
    if isinstance(node, (ast.Set, ast.SetComp)):
        return "set"
    if isinstance(node, ast.Constant) and isinstance(node.value, float):
        return "float"
    return None


def _check_static_args(sf: SourceFile) -> List[Finding]:
    """Flag unhashable/float literals fed to declared static positions.
    Covers `g = jax.jit(f, static_argnums=...)` assignments followed by
    `g(...)` calls, and direct `jax.jit(f, ...)(...)` invocations."""
    out: List[Finding] = []
    if sf.tree is None:
        return out
    jitted: Dict[str, Tuple[Set[int], Set[str]]] = {}
    for node in ast.walk(sf.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            chain = _call_chain(node.value)
            if chain and chain[-1] == "jit" and chain[0] == "jax":
                nums, names = _static_positions(node.value)
                if nums or names:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            jitted[tgt.id] = (nums, names)

    def check_call(call: ast.Call, nums: Set[int], names: Set[str]) -> None:
        def why(kind: str) -> str:
            return ("floats recompile per distinct value" if kind == "float"
                    else "unhashable static args raise at call time")
        for i, arg in enumerate(call.args):
            kind = _bad_static_literal(arg)
            if i in nums and kind and not sf.suppressed(arg.lineno, NAME):
                out.append(Finding(
                    sf.rel, arg.lineno, NAME,
                    f"{kind} literal at static_argnums position {i}: "
                    f"{why(kind)} — pass a hashable config or trace it"))
        for kw in call.keywords:
            kind = _bad_static_literal(kw.value)
            if kw.arg in names and kind and \
                    not sf.suppressed(kw.value.lineno, NAME):
                out.append(Finding(
                    sf.rel, kw.value.lineno, NAME,
                    f"{kind} literal for static_argnames={kw.arg!r}: "
                    f"{why(kind)} — pass a hashable config or trace it"))

    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        if isinstance(node.func, ast.Name) and node.func.id in jitted:
            check_call(node, *jitted[node.func.id])
        elif isinstance(node.func, ast.Call):  # jax.jit(f, ...)(args)
            chain = _call_chain(node.func)
            if chain and chain[-1] == "jit" and chain[0] == "jax":
                check_call(node, *_static_positions(node.func))
    return out


def run(files: List[SourceFile], root: str) -> List[Finding]:
    roots = set(DEFAULT_ROOTS)
    index = _index_functions(files)
    for sf in files:
        if sf.tree is None:
            continue
        for node in ast.walk(sf.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and sf.def_annotation(node, JIT_ENTRY_RE):
                roots.add(node.name)
    findings: List[Finding] = []
    for fi in _reachable(index, roots):
        findings.extend(_TaintChecker(fi.sf, fi.node, fi.qualname).run())
    for sf in files:
        findings.extend(_check_static_args(sf))
    return sorted(set(findings), key=lambda f: (f.path, f.line))
