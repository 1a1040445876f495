"""CLI: `python -m tools.oelint [pass ...]` (make lint).

Exit code 1 on any finding. `--changed-only` restricts file-scanning passes
to files changed vs HEAD (and skips the hlo-budget compile unless a trigger
path changed) for fast local iteration; `--update-budget` regenerates
tools/oelint/hlo_budget.json after an INTENTIONAL collective change.
"""

from __future__ import annotations

import argparse
import os
import sys
import time


def _cpu_env() -> None:
    """CPU-only before anything imports jax: the hlo-budget pass compiles on
    8 virtual host devices and must never claim an accelerator (same
    contract as the Makefile's CPU_ENV / root conftest.py)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()


def main(argv=None) -> int:
    _cpu_env()
    from . import BY_NAME, run_passes
    from .core import repo_root
    from .passes import hlo_budget

    ap = argparse.ArgumentParser(
        prog="python -m tools.oelint",
        description="static-analysis + invariant-guard suite "
                    "(trace-hazard, host-sync, sharding, spmd-divergence, "
                    "hlo-budget, implicit-reshard, lockset, atomicity, "
                    "cond-wait, thread-lifecycle, metrics)")
    ap.add_argument("passes", nargs="*", metavar="PASS",
                    help=f"passes to run (default all): "
                         f"{', '.join(BY_NAME)}")
    ap.add_argument("--changed-only", action="store_true",
                    help="lint only files changed vs HEAD; skip the "
                         "hlo-budget compile unless a trigger path changed")
    ap.add_argument("--update-budget", action="store_true",
                    help="recompile every pinned config and rewrite "
                         "tools/oelint/hlo_budget.json (commit the diff)")
    ap.add_argument("--list", action="store_true", help="list passes")
    args = ap.parse_args(argv)

    if args.list:
        for name, mod in BY_NAME.items():
            first = (mod.__doc__ or "").strip().splitlines()[0]
            print(f"{name:<14s} {first}")
        return 0
    if args.update_budget:
        t0 = time.monotonic()
        path = hlo_budget.update_budget(repo_root())
        print(f"oelint: budget regenerated at {path} "
              f"({time.monotonic() - t0:.1f}s) — review + commit the diff")
        return 0
    for name in args.passes:
        if name not in BY_NAME:
            ap.error(f"unknown pass {name!r}; expected one of "
                     f"{', '.join(BY_NAME)}")

    t0 = time.monotonic()
    findings, timings = run_passes(args.passes or None,
                                   changed_only=args.changed_only)
    try:  # expose run health as gauges (scraped when run in-process)
        from openembedding_tpu.utils import metrics as _metrics
        for n, dt in timings.items():
            _metrics.observe("lint.pass_seconds", dt, "gauge",
                             labels={"pass": n})
        _metrics.observe("lint.findings", float(len(findings)), "gauge")
    except Exception:  # noqa: BLE001 — lint must not die on telemetry
        pass
    for f in findings:
        print(f)
    ran = ", ".join(f"{n} {dt:.1f}s" for n, dt in timings.items())
    total = time.monotonic() - t0
    if findings:
        print(f"\noelint: {len(findings)} finding(s) [{ran}; total "
              f"{total:.1f}s]")
        print("suppress a false positive with "
              "`# oelint: disable=<pass> -- <reason>` (reason mandatory); "
              "regenerate the HLO budget with --update-budget only for "
              "INTENTIONAL collective changes")
        return 1
    print(f"oelint: clean [{ran}; total {total:.1f}s]")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
