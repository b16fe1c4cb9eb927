#!/usr/bin/env python
"""Run the tier-2 test files directly, one pytest process per file,
with per-file timing.

This env's tier-1 gate runs ``pytest tests/ -m 'not slow'`` inside an
870 s budget; the suite is bigger than the budget, so files that sort
late alphabetically — the ``test_zz_*`` resilience/wire drills and the
``test_serving_router*`` fault drills — land AFTER the truncation point
and never execute in tier-1. They are real gates for the serving/
resilience stack and must be run directly; until this runner, that
instruction lived only in CHANGES.md prose.

Usage::

    python -m tools.run_tier2                 # run them all, timed
    python -m tools.run_tier2 --list          # show the file set
    python -m tools.run_tier2 -k failover     # pytest -k passthrough
    python -m tools.run_tier2 --timeout 300   # per-file bound (s)

Exit status is non-zero when any file fails (or times out), so CI can
gate on it exactly like tier-1.
"""
from __future__ import annotations

import argparse
import glob
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the post-truncation set: keep the patterns in sync with README's
# "Testing" section if the truncation point moves
TIER2_PATTERNS = ("tests/test_zz_*.py", "tests/test_serving_router*.py",
                  "tests/test_graft_lint_wave4.py",
                  "tests/test_graft_lint_wave5.py",
                  "tests/test_kernel_hygiene_fixes.py")


def tier2_files() -> list:
    # deduped while keeping pattern order: a file matching two patterns
    # (a test_zz_* drill also named by an explicit entry) must run once
    out = []
    seen = set()
    for pat in TIER2_PATTERNS:
        for f in sorted(glob.glob(os.path.join(REPO, pat))):
            if f not in seen:
                seen.add(f)
                out.append(f)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.run_tier2",
        description="run the post-truncation (tier-2) test files "
                    "directly with per-file timing")
    ap.add_argument("--list", action="store_true",
                    help="print the tier-2 file set and exit")
    ap.add_argument("--timeout", type=float, default=600.0,
                    help="per-file wall-clock bound in seconds "
                         "(default 600)")
    ap.add_argument("-k", metavar="EXPR", default=None,
                    help="forwarded to pytest -k")
    args = ap.parse_args(argv)

    files = tier2_files()
    if args.list:
        for f in files:
            print(os.path.relpath(f, REPO))
        return 0
    if not files:
        print("run_tier2: no tier-2 test files found", file=sys.stderr)
        return 2

    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    results = []
    for f in files:
        rel = os.path.relpath(f, REPO)
        cmd = [sys.executable, "-m", "pytest", f, "-q", "-m", "not slow",
               "-p", "no:cacheprovider"]
        if args.k:
            cmd += ["-k", args.k]
        t0 = time.monotonic()
        try:
            proc = subprocess.run(cmd, cwd=REPO, env=env,
                                  timeout=args.timeout)
            rc = proc.returncode
            if rc == 5 and args.k:
                rc = 0      # -k deselected every test in this file
        except subprocess.TimeoutExpired:
            rc = -1
        dt = time.monotonic() - t0
        results.append((rel, rc, dt))
        print(f"run_tier2: {rel}: "
              f"{'TIMEOUT' if rc == -1 else 'ok' if rc == 0 else 'FAIL'}"
              f" rc={rc} in {dt:.1f}s", flush=True)

    print("\nrun_tier2 summary:")
    width = max(len(r) for r, _, _ in results)
    for rel, rc, dt in results:
        status = "TIMEOUT" if rc == -1 else ("ok" if rc == 0
                                             else f"FAIL({rc})")
        print(f"  {rel:<{width}}  {dt:8.1f}s  {status}")
    total = sum(dt for _, _, dt in results)
    failed = [rel for rel, rc, _ in results if rc != 0]
    print(f"  {'total':<{width}}  {total:8.1f}s  "
          f"{len(results) - len(failed)}/{len(results)} ok")
    if failed:
        print("run_tier2: FAILED: " + ", ".join(failed),
              file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
