"""Runtime invariant guards: the never-re-jit rule as an executable assertion.

The static half of this contract lives in `tools/oelint` (the trace-hazard
pass flags the Python patterns that cause retraces; the hlo-budget pass pins
the compiled collective set). This module is the RUNTIME half: tests and the
soak harness wrap their jitted step functions so that a retrace — a shape
that drifted, a dtype that flipped, a static arg that changed — raises
`RecompileError` at the offending call instead of silently recompiling and
burying seconds of latency in a production step.

Two tools:

- `assert_no_recompile(fn, max_traces=1)` — wrap a function so exceeding the
  trace budget raises. Accepts either a plain Python callable (it is jitted
  here, and the budget is enforced AT TRACE TIME — the error points at the
  exact call that triggered the retrace) or an ALREADY-jitted function (the
  budget is checked after every call against the compilation-cache GROWTH
  since wrap time — `jax.jit` wrappers of one underlying function share a
  cache, so absolute size would count other instances' programs).
  `max_traces` > 1 covers deliberately multi-mode functions (e.g. the
  `_hot_jit` lifecycle fns compile once per mode).

- `trace_counter(*jitted_fns)` — context manager observing how many NEW
  compilations the wrapped block triggered (`.new_traces`), for soak loops
  that want to assert "N more steps, zero new programs" without adopting the
  raising wrapper.

- `collective_fingerprint(fn, *args)` — hash of the ORDERED collective op
  sequence `fn` traces to for these arguments (primitive name, axis names,
  output avals — walked from the jaxpr, nested pjit/shard_map/control-flow
  included). The SPMD contract says this sequence must be identical on every
  process and must survive hot-row refreshes, migrations and placement
  cycles (all content-only by design); tests and the soak harness pin it
  with `assert_collective_fingerprint`, which raises
  `CollectiveMismatchError` with both sequences when the program changed.
  This is the runtime twin of the static spmd-divergence and
  implicit-reshard lint passes (tools/oelint): they catch the Python
  patterns and the compiled reshards, this catches the traced truth.

The recompile guards lean on the jit compilation cache itself
(`fn._cache_size()`), so they measure what XLA actually did, not what the
code intended; the fingerprint leans on `jax.make_jaxpr`, so it is
compile-free and cheap enough for a soak loop.
"""

from __future__ import annotations

import functools
import hashlib
from contextlib import contextmanager
from typing import List, Optional, Tuple

__all__ = ["RecompileError", "CollectiveMismatchError", "TraceCounter",
           "assert_no_recompile", "trace_counter", "collective_sequence",
           "collective_fingerprint", "assert_collective_fingerprint",
           "last_fingerprint"]


class RecompileError(RuntimeError):
    """A guarded jitted function compiled more times than its budget."""


class CollectiveMismatchError(RuntimeError):
    """A pinned collective fingerprint changed: the traced collective
    sequence differs from the one the pin was taken against."""


class TraceCounter:
    """Mutable trace count for one guarded function (exposed as
    `guarded.traces` on `assert_no_recompile` wrappers of plain callables)."""

    def __init__(self, label: str, limit: int):
        self.label = label
        self.limit = int(limit)
        self.traces = 0

    def hit(self) -> None:
        self.traces += 1
        if self.traces > self.limit:
            raise RecompileError(
                f"{self.label!r} traced {self.traces} times (budget "
                f"{self.limit}): a shape/dtype/static-arg changed between "
                "calls — the never-re-jit rule (parallel/sharded.py; "
                "static shapes, content-only refreshes) is broken at this "
                "call site")

    def __repr__(self) -> str:
        return (f"TraceCounter({self.label!r}, traces={self.traces}, "
                f"limit={self.limit})")


def _cache_size(fn) -> Optional[int]:
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:  # noqa: BLE001 — jax internals; degrade to None
        return None


def assert_no_recompile(fn=None, *, max_traces: int = 1,
                        label: Optional[str] = None, **jit_kwargs):
    """Guard `fn` against recompiles. See module doc.

    Plain callable: returns a jitted wrapper; trace #max_traces+1 raises
    RecompileError from inside tracing (the offending call's stack).
    Already-jitted callable (`jax.jit` output, e.g. a trainer's step fn):
    returns a forwarding wrapper that raises when the underlying compilation
    cache grows past the budget. Usable as a decorator:
    `@assert_no_recompile` or `@assert_no_recompile(max_traces=2)`.
    """
    if fn is None:
        return functools.partial(assert_no_recompile, max_traces=max_traces,
                                 label=label, **jit_kwargs)
    name = label or getattr(fn, "__name__", None) or repr(fn)

    if _cache_size(fn) is not None:
        if jit_kwargs:
            raise ValueError(
                f"{name!r} is already jitted; jit kwargs {sorted(jit_kwargs)}"
                " cannot be applied — pass the plain function instead")

        # Budget NEW compilations from wrap time on: `jax.jit(f)` wrappers of
        # the same underlying function share one compilation cache, so the
        # absolute size counts programs other instances (other tables, other
        # tests) compiled — only the delta is this wrapper's to budget.
        base = _cache_size(fn) or 0

        @functools.wraps(fn)
        def guarded(*args, **kwargs):
            out = fn(*args, **kwargs)
            n = _cache_size(fn)
            if n is not None and n - base > max_traces:
                raise RecompileError(
                    f"{name!r} compiled {n - base} new programs (budget "
                    f"{max_traces}): this call triggered a retrace — a "
                    "shape/dtype/static-arg changed (never-re-jit rule, "
                    "parallel/sharded.py)")
            return out

        guarded.trace_count = lambda: (_cache_size(fn) or 0) - base
        return guarded

    import jax
    counter = TraceCounter(name, max_traces)

    def traced(*args, **kwargs):
        counter.hit()  # raises at TRACE time: the stack is the bad call's
        return fn(*args, **kwargs)

    jitted = jax.jit(traced, **jit_kwargs)

    @functools.wraps(fn)
    def guarded(*args, **kwargs):
        return jitted(*args, **kwargs)

    guarded.traces = counter
    guarded.trace_count = lambda: counter.traces
    return guarded


# -- collective fingerprint (the SPMD-contract runtime twin) -----------------

# traced collective primitives (jax.lax); pmean/pmax lower through psum/pmax,
# and shard_map's replication-checking rewrite renames psum to psum2
_COLLECTIVE_PRIMS = {
    "psum", "psum2", "pmax", "pmin", "ppermute", "pbroadcast", "all_to_all",
    "all_gather", "all_gather_invariant", "reduce_scatter", "psum_scatter",
    "psum_invariant",
}


def _walk_jaxpr(jaxpr, seq: List[Tuple[str, str, Tuple[str, ...]]]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in _COLLECTIVE_PRIMS:
            axes = eqn.params.get("axes", eqn.params.get("axis_name"))
            seq.append((name, str(axes),
                        tuple(str(v.aval) for v in eqn.outvars)))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", None)  # ClosedJaxpr
                if inner is not None and hasattr(inner, "eqns"):
                    _walk_jaxpr(inner, seq)
                elif hasattr(sub, "eqns"):           # bare Jaxpr param
                    _walk_jaxpr(sub, seq)


def collective_sequence(fn, *args, **kwargs):
    """Ordered [(primitive, axes, out avals)] of every collective `fn`
    traces to for these arguments, nested jaxprs included. Works on plain
    and jitted callables alike (tracing only — nothing compiles or runs)."""
    import jax
    closed = jax.make_jaxpr(fn)(*args, **kwargs)
    seq: List[Tuple[str, str, Tuple[str, ...]]] = []
    _walk_jaxpr(closed.jaxpr, seq)
    return seq


def primitive_sites(fn, names, *args, **kwargs):
    """[(primitive, name stack, operand and result shapes)] of every equation
    `fn` traces to whose primitive is one of `names`, nested jaxprs included;
    the name stack is the whole chain of `jax.named_scope`s (`trace.scope`
    stages) down to the equation. Tracing only. For structural pins of the
    kind "no scatter over S x capacity rows outside `exchange.full_size`"."""
    import jax
    found = []

    def walk(jaxpr, under):
        for eqn in jaxpr.eqns:
            stack = f"{under}/{eqn.source_info.name_stack}"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, stack)
            if eqn.primitive.name in names:
                found.append((eqn.primitive.name, stack, [
                    tuple(v.aval.shape) for v in (*eqn.invars, *eqn.outvars)
                    if hasattr(v.aval, "shape")]))
    walk(jax.make_jaxpr(fn)(*args, **kwargs).jaxpr, "")
    return found


# the most recent fingerprint computed in this process — postmortem capsules
# (utils/capsule.py) embed it so a dump names the collective program that was
# live at the failure without re-tracing anything
_LAST_FINGERPRINT: Optional[str] = None


def last_fingerprint() -> Optional[str]:
    """The most recently computed/asserted collective fingerprint (None
    before any `collective_fingerprint` call in this process)."""
    return _LAST_FINGERPRINT


def collective_fingerprint(fn, *args, **kwargs) -> str:
    """sha256 (16 hex chars) over `collective_sequence(fn, *args)`: pin it
    once per compiled mode, and any change to which collectives run, in
    what order, over which axes, at what shapes/dtypes changes the hash."""
    global _LAST_FINGERPRINT
    seq = collective_sequence(fn, *args, **kwargs)
    fp = hashlib.sha256(repr(seq).encode()).hexdigest()[:16]
    _LAST_FINGERPRINT = fp
    from . import metrics as _metrics
    _metrics.observe("guard.fingerprints", 1.0)
    return fp


def assert_collective_fingerprint(fn, pinned: str, *args,
                                  label: Optional[str] = None,
                                  **kwargs) -> str:
    """Raise `CollectiveMismatchError` if `fn`'s traced collective sequence
    no longer hashes to `pinned`; returns the (matching) fingerprint. The
    error carries the full current sequence — diff it against the pin
    commit to see which collective moved."""
    global _LAST_FINGERPRINT
    seq = collective_sequence(fn, *args, **kwargs)
    fp = hashlib.sha256(repr(seq).encode()).hexdigest()[:16]
    _LAST_FINGERPRINT = fp
    if fp != pinned:
        name = label or getattr(fn, "__name__", None) or repr(fn)
        from . import metrics as _metrics
        _metrics.observe("guard.fingerprint_trips", 1.0)
        raise CollectiveMismatchError(
            f"{name!r}: traced collective sequence changed (fingerprint "
            f"{fp} != pinned {pinned}) — the SPMD collective program is "
            "supposed to be refresh/migration/resize-invariant. Current "
            f"sequence: {seq}")
    return fp


class _TraceDelta:
    """Live view of new compilations since the `trace_counter` block began."""

    def __init__(self, fns):
        self._fns = fns
        self._before = [(_cache_size(f) or 0) for f in fns]

    @property
    def per_fn(self):
        return [(_cache_size(f) or 0) - b
                for f, b in zip(self._fns, self._before)]

    @property
    def new_traces(self) -> int:
        return sum(self.per_fn)


@contextmanager
def trace_counter(*jitted_fns):
    """`with trace_counter(step_fn) as tc:` ... `assert tc.new_traces == 0`.

    Counts NEW jit compilations of the given already-jitted functions inside
    the block (live: `.new_traces` is current at any point, including after
    exit). Functions without a compilation cache contribute 0.
    """
    yield _TraceDelta(jitted_fns)
