"""Per-kernel bench-ratio regression gate.

``bench_kernels.py`` prints pallas-vs-XLA ratios (``xla_ms /
pallas_ms``, higher is better); a report carries them under
``extra.kernels_vs_xla.results``. This tool compares a report against
the recorded per-kernel bests in ``artifacts/kernel_ratios_best.json``
and fails when any measured direction drops more than ``--tolerance``
below its best — a perf regression that per-run eyeballing misses when
only one kernel of eleven slips.

Distinct from ``tools/kernel_baseline.py``: that module maintains the
*shipped* post-selection floor the kernel gate enforces (with decay
semantics for noisy captures); this one tracks *raw* bench ratios and
only ever ratchets up, so it answers "is this kernel slower than it has
ever been measured?" rather than "is dispatch still shipping a win?".

Usage::

    python -m tools.check_bench_ratios report.json
    python -m tools.check_bench_ratios report.json --update   # new bests

Rows carrying a ``*_error`` field or no ``ratio`` are skipped (a
transient per-case compile failure must not discard the run). Keys in
the bests file that the report did not measure are skipped too —
partial bench runs are normal. ``--update`` writes back
``max(best, measured)`` per key and records first-seen kernels.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

DEFAULT_BEST = os.path.join("artifacts", "kernel_ratios_best.json")


def report_ratios(report: dict) -> dict:
    """{'kernel.direction': ratio} for every cleanly measured direction."""
    results = (report.get("extra", {})
               .get("kernels_vs_xla", {})
               .get("results") or {})
    out = {}
    for name, entry in results.items():
        if not isinstance(entry, dict):
            continue
        for tag, row in entry.items():
            if not isinstance(row, dict) or "ratio" not in row:
                continue
            if any(k.endswith("_error") for k in row):
                continue
            out[f"{name}.{tag}"] = float(row["ratio"])
    return out


def load_best(path: str) -> dict:
    if not os.path.exists(path):
        return {}
    with open(path) as f:
        doc = json.load(f)
    return {k: float(v) for k, v in (doc.get("ratios") or {}).items()}


def save_best(path: str, ratios: dict) -> None:
    doc = {
        "note": "best-ever raw pallas-vs-xla bench ratios "
                "(xla_ms/pallas_ms, higher is better); ratchets up only. "
                "Gate: tools/check_bench_ratios.py",
        "ratios": {k: round(float(v), 3) for k, v in sorted(ratios.items())},
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")
    os.replace(tmp, path)


def check(measured: dict, best: dict, tolerance: float):
    """-> (regressions, improvements, new_keys). A regression is a
    measured ratio below ``best * (1 - tolerance)``."""
    regressions, improvements, new = [], [], []
    for key, ratio in sorted(measured.items()):
        if key not in best:
            new.append(key)
            continue
        floor = best[key] * (1.0 - tolerance)
        if ratio < floor:
            regressions.append((key, ratio, best[key], floor))
        elif ratio > best[key]:
            improvements.append((key, ratio, best[key]))
    return regressions, improvements, new


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="check_bench_ratios",
        description="fail when bench kernel ratios drop below best-ever")
    ap.add_argument("report", help="bench report JSON")
    ap.add_argument("--best", default=DEFAULT_BEST,
                    help=f"recorded-bests file (default {DEFAULT_BEST})")
    ap.add_argument("--tolerance", type=float, default=0.15,
                    help="allowed fractional drop below best (default 0.15)")
    ap.add_argument("--update", action="store_true",
                    help="write back max(best, measured) per kernel")
    args = ap.parse_args(argv)

    try:
        with open(args.report) as f:
            report = json.load(f)
    except (OSError, ValueError) as e:
        print(f"check_bench_ratios: cannot read report: {e}",
              file=sys.stderr)
        return 2
    measured = report_ratios(report)
    if not measured:
        print("check_bench_ratios: report has no clean kernel ratios "
              "(extra.kernels_vs_xla.results)", file=sys.stderr)
        return 2
    best = load_best(args.best)

    regressions, improvements, new = check(measured, best, args.tolerance)
    for key, ratio, prev, floor in regressions:
        print(f"REGRESSION {key}: ratio {ratio:.3f} < floor {floor:.3f} "
              f"(best {prev:.3f}, tolerance {args.tolerance:.0%})")
    for key, ratio, prev in improvements:
        print(f"improved   {key}: {prev:.3f} -> {ratio:.3f}")
    for key in new:
        print(f"new        {key}: {measured[key]:.3f} (no recorded best)")
    skipped = sorted(set(best) - set(measured))
    if skipped:
        print(f"not measured this run: {', '.join(skipped)}")

    if args.update:
        merged = dict(best)
        for key, ratio in measured.items():
            merged[key] = max(merged.get(key, 0.0), ratio)
        save_best(args.best, merged)
        print(f"wrote {len(merged)} best(s) to {args.best}")

    if regressions:
        print(f"check_bench_ratios: {len(regressions)} regression(s)")
        return 1
    print(f"check_bench_ratios: OK — {len(measured)} measured, "
          f"{len(new)} new, {len(improvements)} improved")
    return 0


if __name__ == "__main__":
    sys.exit(main())
