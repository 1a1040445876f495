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
"""

from __future__ import annotations

import os

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
    return path


def entry_count(path: str) -> int:
    """Number of cached executables under `path` (0 when it does not exist)."""
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0
