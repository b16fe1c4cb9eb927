"""Kernel-gate baseline lifecycle (VERDICT r4 next-round #7).

The regression floor in ``artifacts/kernel_baseline.json`` was seeded from
the r3 raw pallas-vs-xla ratios, which grandfathers sub-1.0 losses (GQA
fwd_bwd 0.837): a future 0.76 would pass the no-regression check. The fix:

- after the first clean shipped-ratio capture, the baseline is re-seeded
  from **post-selection shipped ratios** (what dispatch actually routes,
  i.e. the numbers users get) and stamped ``kind: "shipped"`` +
  ``seeded_at_unix``;
- later clean captures keep-best per key, so the floor only ratchets up;
- the gate *fails* (not skips) when asked to validate a capture older than
  the baseline seed — replayed stale evidence can never read as green.

Reference discipline: tools/check_op_benchmark_result.py compares against a
stored develop-branch baseline and refuses mismatched artifacts.
"""
from __future__ import annotations

import json
import os


def shipped_ratios(capture: dict, clean_only: bool = False) -> dict:
    """{'case.direction': shipped_ratio} for every measured direction.
    ``clean_only`` drops rows carrying a ``*_error`` field — one case
    that failed to compile or fit must not discard the other cases'
    measurements."""
    out = {}
    for name, entry in (capture.get("results") or {}).items():
        for tag, row in entry.items():
            if not isinstance(row, dict) or "shipped_ratio" not in row:
                continue
            if clean_only and any(k.endswith("_error") for k in row):
                continue
            out[f"{name}.{tag}"] = row["shipped_ratio"]
    return out


def capture_errors(capture: dict) -> list:
    errs = [f"{name}.{tag}.{k}"
            for name, entry in (capture.get("results") or {}).items()
            for tag, row in entry.items() if isinstance(row, dict)
            for k in row if k.endswith("_error")]
    if capture.get("error"):
        errs.append("error")
    return errs


def capture_time(capture: dict, path: str = None) -> float:
    """Embedded capture timestamp, falling back to file mtime for pre-r5
    captures that predate the ``captured_at_unix`` field."""
    ts = capture.get("captured_at_unix")
    if ts:
        return float(ts)
    if path and os.path.exists(path):
        return os.path.getmtime(path)
    return 0.0


def is_stale(capture: dict, baseline: dict, capture_path: str = None) -> bool:
    """True when the capture predates the baseline's seed: the gate must
    fail rather than validate replayed evidence against a newer floor."""
    seeded = baseline.get("seeded_at_unix")
    if not seeded:
        return False  # pre-r5 raw baseline carries no seed stamp
    # a seeded baseline implies post-r5 bench_kernels.py, which always
    # embeds captured_at_unix — a capture without it is a pre-r5 replay,
    # and the file-mtime fallback is forgeable by cp/checkout (mtime=now)
    if not capture.get("captured_at_unix"):
        return True
    return capture_time(capture, capture_path) < float(seeded) - 1.0


def reseed(capture: dict, baseline_path: str,
           capture_path: str = None) -> bool:
    """Re-seed the baseline from the capture's clean shipped ratios.

    Per-case: rows with errors are skipped, not the whole capture —
    all-or-nothing would let one failing case keep the grandfathered raw
    floor alive forever.
    Merge per key against a shipped baseline: a higher fresh ratio ratchets
    the floor up; a lower one decays it geometrically (sqrt(old*fresh))
    instead of pinning the best-ever — one noisy high measurement must not
    fail every honest capture after it. Real regressions are still caught:
    run the gate (tests/test_kernel_gate.py) against the OLD floor before
    calling this, and the absolute shipped floor (0.95) is
    baseline-independent.
    A raw (pre-r5) baseline is replaced outright. Returns False when no
    clean shipped ratios exist.
    """
    ratios = shipped_ratios(capture, clean_only=True)
    if not ratios:
        return False
    old = {}
    if os.path.exists(baseline_path):
        try:
            with open(baseline_path) as f:
                old = json.load(f)
        except Exception:
            old = {}
    merged = dict(ratios)
    if old.get("kind") == "shipped":
        for k, v in (old.get("ratios") or {}).items():
            if k not in merged:
                merged[k] = v  # a case this capture didn't run: keep floor
            elif v > merged[k]:
                merged[k] = (v * merged[k]) ** 0.5  # decay toward fresh
    new = {
        "note": "post-selection shipped-ratio floor for "
                "tests/test_kernel_gate.py; ratchets up on improvement, "
                "decays geometrically on lower remeasure "
                "(tools/kernel_baseline.py)",
        "kind": "shipped",
        "seeded_at_unix": capture_time(capture, capture_path),
        "ratios": {k: round(float(v), 3) for k, v in sorted(merged.items())},
    }
    tmp = baseline_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(new, f, indent=1)
    os.replace(tmp, baseline_path)
    return True
