"""Test harness: simulate an 8-device TPU mesh on CPU.

Mirrors the reference's test strategy of simulating a multi-process cluster inside one
test binary (`core::MultiProcess` fork harness, `entry/c_api_test.h:195,285`): here one
process hosts 8 virtual XLA CPU devices and shard_map/pjit run real collectives over
them (SURVEY.md §4 implication (a)).
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = _flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"
# The suite's parity tests assert EXACT (1e-5-ish) mesh-vs-single-device
# agreement, so the suite baseline pins the lossless wire format; the bf16
# production default and int8 are covered explicitly in tests/test_wire.py
# (which passes wire=... to MeshTrainer, overriding this env default).
os.environ.setdefault("OETPU_WIRE", "fp32")

import jax

# 63-bit hashed id spaces need int64 ids (`meta.HASH_VOCABULARY_THRESHOLD`)
jax.config.update("jax_enable_x64", True)

# ONE persistent compile cache a test process (an xdist worker, or the one
# process of a serial run), in a directory of its own that goes with it: a
# program whose HLO the process compiled before is loaded, not compiled again.
# New trainers, `MeshTrainer.init`'s programs (made anew at every call) and a
# path beside its reference compile the same HLO many times a file; tracing is
# as it was. The key leaves metadata out (scope names, source lines), so a
# loaded executable's TEXT carries the names of the trace that wrote it: a
# test that changes nothing but those, or counts backend compiles, asks for
# `no_compile_cache`. `JAX_COMPILATION_CACHE_DIR`, where set, is left alone.
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    import atexit
    import shutil
    import tempfile
    _programs = tempfile.mkdtemp(prefix="oetpu-tests-programs-")
    atexit.register(shutil.rmtree, _programs, ignore_errors=True)
    jax.config.update("jax_compilation_cache_dir", _programs)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def pytest_configure(config):
    # tier-1 (ROADMAP.md) runs `-m 'not slow'` under a hard wall-clock
    # timeout; multi-epoch training runs that have a cheaper pinned-parity
    # counterpart elsewhere opt out of that window with this marker.
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 timed window")


import pytest


@pytest.fixture(autouse=True)
def _windows_fold_in_their_own_test():
    """`jit_train_many`'s dispatch object leaves a window's counters pending
    until they are ready or somebody reads the registry (`model.
    _PendingWindows`); read it at every test's end, so that no window of one
    test is folded into the series a later test counts."""
    yield
    from openembedding_tpu.utils import metrics
    metrics.report()


@pytest.fixture
def no_compile_cache():
    """Every backend compile of this test is a compile (the process's cache
    is put back afterwards)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keep = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    cc.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", keep)
    cc.reset_cache()
