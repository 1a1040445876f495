"""Persistent XLA compile cache for PROCESS ENTRY POINTS (`chip_smoke.py`,
`python -m openembedding_tpu.serving`, `examples/criteo_deepctr.py`).

Never called at library import and never under pytest: a cache directory is a
process-wide JAX setting, so only the code that owns the process sets it.

Placement rule: where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself
and this module sets no other directory. Where it is not, the cache lives at
ONE fixed path inside the checkout (`<repo>/.jax_cache/<platform>`, git-ignored)
— the directory is part of the cache key's context, so a temp name, pid or
timestamp would never hit. The per-platform subdirectory keeps XLA:CPU entries
from a rehearsal out of the directory a chip run reads: CPU AOT results are
tied to the build host's CPU model and only log "machine type doesn't match"
on another host.

The account of it (`listen`, `entry`). JAX hands every jaxpr trace, MLIR
lowering and backend compile to `jax.monitoring` listeners with the traced
function's name, and the persistent cache's hits, misses and retrieval
seconds beside them. ONE pair of listeners, registered once a process, folds
them into the metrics registry under `fn` = the program's entry point on
whose call they fired (`entry("train_many")` around a dispatch of
`jit_train_many`, `entry("init")` around `Trainer.init`), JAX's own
`fun_name` where no entry point is running, `other` where JAX names none:

    compile.trace_s{fn=}       seconds of jaxpr trace + lowering to MLIR
    compile.backend_s{fn=}     seconds of backend compile (the cache missed,
                               or holds no such program yet)
    compile.cache_load_s{fn=}  seconds of reading executables out of the cache
    compile.cache_hits{fn=}    executables the cache held
    compile.cache_misses{fn=}  executables compiled and written to it
    compile.executables{fn=}   executables made or loaded: 2 after ONE trace
                               is a second signature of the same function
                               (another sharding or committed-ness of an
                               argument), which `trainer.traces{fn=}` cannot
                               see

and each lowering, compile and cache load goes to the flight recorder
(`trace.event("compile", ...)`; a trace from `RECORDED_TRACE_S` on: one scan
traces thousands of small functions inside it, and the recorder is bounded),
so `/tracez` and a capsule say which call compiled and for how long. Traces
nest (a jitted function traced inside another's trace reports its own
seconds, and the outer one's hold them): `compile.trace_s` counts every
second once. The listeners run only when JAX traces or compiles: a steady
train loop never reaches them.
"""

from __future__ import annotations

import contextvars
import os
import threading
import time
from contextlib import contextmanager

from . import metrics

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CACHE_ROOT = os.path.join(_REPO_ROOT, ".jax_cache")


def cache_dir(platform: str) -> str:
    """Where this process's compile cache lives (pure; touches no JAX state)."""
    return os.environ.get(ENV_VAR) or os.path.join(CACHE_ROOT, platform)


def enable() -> str:
    """Turn the persistent compile cache on for this process; returns its
    directory. Call once, from `main()`, before the first compile."""
    import jax
    path = cache_dir(jax.default_backend())
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    # the step programs take seconds to minutes, but a run also compiles
    # dozens of sub-second programs (init, eager serving lookups); the default
    # 1 s floor would recompile every one of them on each start
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    listen()
    return path


def entry_count(path: str) -> int:
    """Number of cached executables under `path` (0 when it does not exist)."""
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0


# -- the account of traces, compiles and cache loads -------------------------

SERIES = {"trace_s": "compile.trace_s", "backend_s": "compile.backend_s",
          "cache_load_s": "compile.cache_load_s",
          "cache_hits": "compile.cache_hits",
          "cache_misses": "compile.cache_misses",
          "executables": "compile.executables"}
_TRACE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                 "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower"}
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}

_entry: contextvars.ContextVar = contextvars.ContextVar(
    "oetpu_compile_entry", default=None)
_listening = threading.Lock()
_listens = False


RECORDED_TRACE_S = 0.01  # shorter traces are counted, not recorded


class _Thread(threading.local):
    """This thread's open account. What the cache said during the backend
    compile the thread is in: those events carry no function name and fire
    BEFORE the compile's own event, which does and closes the account of
    that executable. And the traces already counted that a trace still
    running may hold: (start, seconds), the roots of the finished ones."""
    cache_hits = 0
    cache_misses = 0
    cache_load_s = 0.0

    def __init__(self):
        self.counted = []


_thread = _Thread()


def _uncounted(secs: float) -> float:
    """The part of a trace's or a lowering's `secs` that no event inside it
    has counted: events fire at their END, so what ran inside this one has
    fired already and started no earlier than it did."""
    start = time.perf_counter() - secs - 1e-5
    counted, inside = _thread.counted, 0.0
    while counted and counted[-1][0] >= start:
        inside += counted.pop()[1]
    counted.append((start, secs))
    if len(counted) > 4096 and counted[0][0] < start - 3600.0:
        # roots of an hour ago: no trace still running can hold them
        del counted[:sum(1 for s, _ in counted if s < start - 3600.0)]
    return max(secs - inside, 0.0)


def listen() -> None:
    """Register the pair of listeners (module doc); idempotent."""
    global _listens
    if _listens:
        return
    with _listening:
        if _listens:
            return
        import jax
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listens = True


@contextmanager
def entry(fn: str):
    """Mark the calling context as inside the program's entry point `fn`:
    whatever JAX traces, compiles or loads until the block ends is `fn`'s."""
    listen()
    token = _entry.set(fn)
    try:
        yield
    finally:
        _entry.reset(token)


def _add(fn: str, **amounts) -> None:
    labels = {"fn": fn}
    for name, amount in amounts.items():
        metrics.observe(SERIES[name], amount, "sum", labels=labels)


def _on_event(event: str, **kw) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        setattr(_thread, name, getattr(_thread, name) + 1)


def _on_duration(event: str, secs: float, **kw) -> None:
    if event == _LOAD_EVENT:
        _thread.cache_load_s += secs
        return
    stage = _TRACE_EVENTS.get(event)
    if stage is None and event != _BACKEND_EVENT:
        return
    fun_name = str(kw.get("fun_name") or "other")
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        fun_name = fun_name[4:-1]  # lowering and compile say `jit(f)`, the trace `f`
    fn = _entry.get() or fun_name
    if stage is not None:
        _add(fn, trace_s=_uncounted(secs))
        if stage == "trace" and secs < RECORDED_TRACE_S:
            return
    else:
        hits, misses, load_s = (_thread.cache_hits, _thread.cache_misses,
                                _thread.cache_load_s)
        _thread.cache_hits = _thread.cache_misses = 0
        _thread.cache_load_s = 0.0
        stage = "cache_load" if hits else "backend"
        # the compile's event spans the cache's lookup: on a hit it IS the
        # load. All six series exist from a function's first executable on.
        _add(fn, trace_s=0.0, executables=1, cache_hits=hits,
             cache_misses=misses, cache_load_s=load_s,
             backend_s=0.0 if hits else secs)
    from . import trace  # lazy: trace imports jax's profiler
    trace.event("compile", stage, fn=fn, fun_name=fun_name,
                seconds=round(secs, 6))
