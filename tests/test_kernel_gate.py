"""Kernel-performance regression gate (VERDICT r3 #7).

Reference discipline: tools/ci_op_benchmark.sh + check_op_benchmark_result.py
CI-gate kernel perf by threshold comparison against a stored baseline. Here
the gate validates an on-chip capture: the JSON line ``python
bench_kernels.py`` prints on a TPU, saved to
``artifacts/tpu_capture/bench_kernels.json``:

1. **Shipped never loses**: every ``shipped_ratio`` (dispatch-routed impl
   vs plain XLA) must be >= 0.95 — the routing layer can always fall back
   to XLA, so a sustained loss is a routing bug, not noise.
2. **No silent regression**: raw Pallas ratios must not drop more than 10%
   below the stored baseline (``artifacts/kernel_baseline.json``).
3. **No errors inside the capture**: an artifact with ``*_error`` fields is
   the r3 "incoherent snapshot" failure mode and fails the gate.

Skips when no TPU capture exists (none is committed, and nothing writes
one automatically: run bench_kernels.py on the chip, save its line, then
run this file).
"""
from __future__ import annotations

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CAPTURE = os.path.join(REPO, "artifacts", "tpu_capture",
                       "bench_kernels.json")
BASELINE = os.path.join(REPO, "artifacts", "kernel_baseline.json")

SHIPPED_FLOOR = 0.95      # >=1.0 contract minus timing noise
REGRESSION_TOLERANCE = 0.90  # fresh raw ratio must be >= 90% of baseline

_spec = importlib.util.spec_from_file_location(
    "kernel_baseline", os.path.join(REPO, "tools", "kernel_baseline.py"))
kb = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(kb)


def _load_baseline():
    if not os.path.exists(BASELINE):
        return None
    with open(BASELINE) as f:
        return json.load(f)


def _load_capture():
    if not os.path.exists(CAPTURE):
        pytest.skip("no on-chip bench_kernels capture at "
                    "artifacts/tpu_capture/bench_kernels.json")
    with open(CAPTURE) as f:
        cap = json.load(f)
    if cap.get("platform") != "tpu":
        pytest.skip(f"capture platform is {cap.get('platform')!r}, not tpu")
    base = _load_baseline()
    if base is not None and kb.is_stale(cap, base, CAPTURE):
        # FAIL, not skip (VERDICT r4 #7): once the baseline is seeded from
        # a fresh shipped-ratio capture, a replayed older file is stale
        # evidence and must never validate green
        pytest.fail(
            "capture predates the kernel-baseline seed "
            f"(capture {kb.capture_time(cap, CAPTURE):.0f} < seed "
            f"{base.get('seeded_at_unix', 0):.0f}): replayed stale "
            "evidence — recapture on the chip")
    if not any("shipped_ratio" in row
               for entry in (cap.get("results") or {}).values()
               for row in entry.values()):
        # a capture from before the shipped-impl measurement existed can
        # contain errors that are already fixed in-tree — gating it would
        # fail on stale evidence; the gate arms on the first fresh capture
        pytest.skip("capture predates shipped-ratio measurement "
                    "(pre-r4 bench_kernels.py); recapture needed")
    return cap


def test_capture_has_no_errors():
    cap = _load_capture()
    errs = [f"{name}.{tag}.{k}"
            for name, entry in (cap.get("results") or {}).items()
            for tag, row in entry.items()
            for k in row if k.endswith("_error")]
    assert not errs, (
        "capture contains per-kernel errors (r3 weak #3 — recapture after "
        f"the fixes): {errs}")
    assert not cap.get("error"), cap.get("error")


def test_shipped_impl_never_loses_to_xla():
    cap = _load_capture()
    rows = [(f"{name}.{tag}", row["shipped_ratio"])
            for name, entry in (cap.get("results") or {}).items()
            for tag, row in entry.items() if "shipped_ratio" in row]
    if not rows:
        pytest.skip("capture predates shipped-ratio measurement "
                    "(pre-r4 bench_kernels.py); recapture needed")
    losers = [(n, r) for n, r in rows if r < SHIPPED_FLOOR]
    assert not losers, (
        f"dispatch ships an impl measurably slower than XLA: {losers} "
        f"(floor {SHIPPED_FLOOR}); per-direction routing must fall back")


def test_no_regression_vs_baseline():
    cap = _load_capture()
    base = _load_baseline()
    if base is None:
        pytest.skip("no stored kernel baseline")
    # a shipped-kind baseline (post-r5 reseed) floors what dispatch actually
    # routes; the legacy raw baseline floors the raw pallas ratios
    field = "shipped_ratio" if base.get("kind") == "shipped" else "ratio"
    fresh = {f"{name}.{tag}": row[field]
             for name, entry in (cap.get("results") or {}).items()
             for tag, row in entry.items() if field in row}
    regressions = []
    for key, b in (base.get("ratios") or {}).items():
        r = fresh.get(key)
        if r is not None and r < b * REGRESSION_TOLERANCE:
            regressions.append((key, b, r))
    assert not regressions, (
        f"kernel ratios regressed >10% vs baseline: {regressions}")
