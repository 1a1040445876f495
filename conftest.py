"""Repo-level pytest bootstrap.

Tests run on a virtual 8-device CPU mesh (SURVEY.md §4): the mesh proves
correctness and counts bytes and collectives, and it needs no accelerator. A
chip belongs to one process at a time, so a test run must never claim one —
before any backend initializes we (a) point XLA at 8 virtual host devices and
(b) flip jax's platform selection to cpu, whatever `JAX_PLATFORMS` says. The
chip is for `chip_smoke.py` and `python3 -m benchmark.run` (README "Running
it").
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax

jax.config.update("jax_platforms", "cpu")
