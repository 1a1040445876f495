"""Bring-up guards: where the compile cache goes, and that the chip entry
points refuse to run off-chip unless the caller asked for the CPU by name.

Cheap by design (tier-1 runs against a wall-clock budget): the entry-point
tests exit at the device check, before a model is built. The full three-stage
CPU rehearsal is `@pytest.mark.slow` (`make chip-smoke-cpu`)."""

import json
import os
import subprocess
import sys

import pytest

from openembedding_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout=600, **env_over):
    """Run a repo entry point in a child whose env has no JAX_PLATFORMS unless
    `env_over` names one (children never inherit the suite's device flags)."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "XLA_FLAGS", compile_cache.ENV_VAR)}
    env.update(env_over)
    return subprocess.run([sys.executable] + args, cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_cache_dir_follows_env_else_fixed_checkout_path(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/some/dir")
    assert compile_cache.cache_dir("tpu") == "/some/dir"
    monkeypatch.delenv(compile_cache.ENV_VAR)
    want = os.path.join(REPO, ".jax_cache", "tpu")
    assert compile_cache.cache_dir("tpu") == want
    assert compile_cache.cache_dir("tpu") == want  # no pid/time in the path
    # a CPU rehearsal never writes where a chip run reads
    assert compile_cache.cache_dir("cpu") != want


def test_enable_sets_no_directory_when_env_places_it(tmp_path):
    """With the env var set JAX reads it itself and `enable()` sets nothing
    else; once it is gone the same call lands on the fixed checkout path."""
    code = ("import os, jax\n"
            "from openembedding_tpu.utils import compile_cache as cc\n"
            "print(cc.enable(), jax.config.jax_compilation_cache_dir)\n"
            "del os.environ[cc.ENV_VAR]\n"
            "print(cc.enable(), jax.config.jax_compilation_cache_dir)\n")
    placed = str(tmp_path / "placed")
    p = _run(["-c", code], JAX_PLATFORMS="cpu",
             **{compile_cache.ENV_VAR: placed})
    assert p.returncode == 0, p.stderr
    fixed = os.path.join(REPO, ".jax_cache", "cpu")
    assert p.stdout.split() == [placed, placed, fixed, fixed]


def test_entry_point_refuses_to_run_without_a_tpu():
    """JAX_PLATFORMS unset on a machine with no TPU: jax falls back to the
    CPU by itself, and the entry point must exit non-zero before any stage
    instead of printing a result from it."""
    p = _run(["chip_smoke.py"])
    assert p.returncode != 0, p.stdout
    assert p.stdout.strip() == "", p.stdout  # no JSON line, no *_per_chip
    assert "no TPU" in p.stderr, p.stderr[-2000:]


def test_chip_smoke_cpu_needs_explicit_sizes():
    """JAX_PLATFORMS=cpu alone (this sandbox exports it) is not a rehearsal
    request: without explicit sizes the script still refuses."""
    p = _run(["chip_smoke.py"], JAX_PLATFORMS="cpu")
    assert p.returncode != 0 and p.stdout.strip() == "", (p.stdout, p.stderr)


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal(tmp_path):
    """All three stages at a tiny size on 4 virtual CPU devices (S = 4: real
    shards, real collectives), twice against one cache directory: the second
    run compiles nothing."""
    args = ["chip_smoke.py", "--vocabulary", "65536", "--batch", "256",
            "--scan-steps", "4"]
    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           compile_cache.ENV_VAR: str(tmp_path / "cache")}
    runs = []
    for _ in range(2):
        p = _run(args, **env)
        assert p.returncode == 0, p.stderr[-2000:]
        report, verdict = map(json.loads, p.stdout.splitlines())
        # the last line is the driver's contract: these keys and no others
        assert verdict == {"ok": True, "device": {
            "platform": "cpu", "kind": "cpu", "count": 4}}
        runs.append(report["report"])
    first, second = runs
    for out in runs:
        assert out["stages"]["mesh"]["shards"] == 4
        assert out["stages"]["mesh"]["wire_cost"]["collectives_per_step"] > 0
    assert first["compile_cache"]["entries_after"] > 0
    assert (second["compile_cache"]["entries_after"]
            == second["compile_cache"]["entries_before"])
    assert all(s["compiled"] == 0 for s in second["stages"].values())
